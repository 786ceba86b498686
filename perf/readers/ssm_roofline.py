"""The share of its roofline that the state-space step reaches in the batched decode
programs, with the work and the kernel time taken from the SAME seconds: the runner reads
the program's counters when the trace goes on and when it goes off (`counters_traced`), so
the bytes of state the steps rewrote (`hivemind_moe_ssm_state_bytes_total`, `path=batched`:
the program's own count, from the shapes and the live rows) are those of the traced
programs. The least time for them (`perf/flops_nemotron.py`: every byte read once and
written once, 4 FLOPs a state element a row), per state-space program counted
(`hivemind_moe_decode_calls_total` times the share of the span's blocks that are mixers, `M`
in the configuration's `hybrid_override_pattern`: a cohort runs every block once; a counter
lags its program by at most a cohort, at both edges), times the programs in the trace that
hold the scope, over the device time of the scope's operations there (`scopes`: the runner
attributes a program's operations to the named scope their `op_name` lies in) AND of
``staged``, where the runner gives it: the compiler's own asynchronous copies of the rows'
states into on-chip memory, which are where a state is read from HBM on a chip whose
compiler stages it (the v5e: the scope's operations alone read 97 % apart and 156 % joined,
my chip runs, PR 51). Where those copies overlap the step the share is a lower bound. A runner
that gives no `scopes` or does not read the counters at the trace's edges, a program
without the counter or a trace without the scope's operations gives nothing."""

from perf import flops, flops_nemotron
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta

REWRITTEN = "hivemind_moe_ssm_state_bytes_total"


def read(obs, scope, staged=None):
    entry = (obs.get("scopes") or {}).get(scope)
    edges = obs.get("counters_traced")
    if not entry or not entry["seconds"] or not entry["runs"] or not edges or REWRITTEN not in edges["after"]:
        return None
    traced = {"counters": edges}
    rewritten = delta(traced, {"metric": REWRITTEN, "series": "path=batched"})
    model = obs["config"]["model"]
    pattern = model["hybrid_override_pattern"]
    programs = delta(traced, {"metric": "hivemind_moe_decode_calls_total", "series": "path=batched"}) * pattern.count("M") / len(pattern)
    if not programs or not rewritten:
        return None
    rows = rewritten / flops_nemotron.ssm_row_state_bytes(model)
    needed = flops.roofline_seconds(rows * flops_nemotron.ssm_step_flops(model), flops_nemotron.ssm_step_bytes(rewritten),
                                    peak_for(obs["device"]["kind"]))
    staging = ((obs.get("scopes") or {}).get(staged) or {}).get("seconds", 0.0)
    obs.setdefault("notes", []).append(
        f"{scope}: {programs:.0f} state-space programs counted between the trace's edges, {rows / programs:.1f} rows a program, "
        f"{needed['bound']}-bound, {needed['seconds'] / programs * 1e6:.1f} us a program at the roofline; {entry['runs']:.0f} programs "
        f"traced, {entry['seconds'] / entry['runs'] * 1e6:.1f} us a program in the scope's operations and "
        f"{staging / entry['runs'] * 1e6:.1f} us in the copies that stage the rows' states")
    return 100.0 * (needed["seconds"] / programs) * entry["runs"] / (entry["seconds"] + staging)

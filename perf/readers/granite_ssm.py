"""Three readings of the state-space blocks' batched decode programs in a span whose blocks
are told by `layer_types` (a `granitemoehybrid` configuration: `perf/flops_granite.py`'s key
names), with the work and the device time taken from the SAME seconds: the runner reads the
program's counters when the trace goes on and when it goes off (`counters_traced`), so the
bytes of state the steps rewrote (`hivemind_moe_ssm_state_bytes_total`, `path=batched`: the
program's own count, from the shapes and the live rows) and the programs and rows counted
(`hivemind_moe_decode_calls_total` / `..._steps_total`, `path=batched`, times the share of the
span's blocks that are state-space blocks: a cohort runs every block once; a counter lags its
program by at most a cohort, at both edges) are those of the traced programs. By ``measure``:

- ``step_roofline``: `ssm_step_roofline`'s definition. The least time for the steps (every
  byte of state and window read once and written once, 4 FLOPs a state element a row) per
  state-space program counted, times the programs in the trace that hold ``scope``, over the
  device time of the scope's operations there AND of ``staged`` (the compiler's own
  asynchronous copies of the rows' states into on-chip memory: where a state is read from
  HBM on a chip whose compiler stages it). Where those copies overlap the step the share is a
  lower bound.
- ``block_roofline``: the least time for one WHOLE state-space program (`ssm_program_bytes`:
  the block's parameters once, by the bytes the runner read off the arrays themselves,
  `param_bytes`; the live rows' states and windows in and out; their hidden states in and
  out) over the mean device time of the traced programs named ``program`` (`programs`: the
  profiler's XLA Modules line). The bytes are a LOWER bound of what the program moves, so the
  share cannot honestly pass 100 %.
- ``mixer_share``: the device time of the operations of ``scopes`` and of ``staged`` over the
  device time of the programs named ``program``, in per cent: whether the mechanism or the
  MLP's weights pace the block.

A runner that gives no `scopes` / `programs` / `param_bytes` or does not read the counters at
the trace's edges, a program without the counter (a parent commit) or a trace without the
scope's operations gives nothing."""

from perf import flops, flops_granite
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta

REWRITTEN = "hivemind_moe_ssm_state_bytes_total"


def _traced_work(obs):
    """(state-space programs, their live rows, the bytes they rewrote) between the trace's edges, or None."""
    edges = obs.get("counters_traced")
    if not edges or REWRITTEN not in edges["after"]:
        return None
    traced = {"counters": edges}
    kinds = obs["config"]["model"]["layer_types"]
    share = kinds.count(flops_granite.MAMBA) / len(kinds)
    programs, rows = (delta(traced, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"}) * share
                      for name in ("calls", "steps"))
    rewritten = delta(traced, {"metric": REWRITTEN, "series": "path=batched"})
    return (programs, rows, rewritten) if programs and rewritten else None


def read(obs, measure, scope=None, scopes=(), staged=None, program=None):
    seconds_of = lambda name: ((obs.get("scopes") or {}).get(name) or {}).get("seconds", 0.0)
    ran = (obs.get("programs") or {}).get(program) if program else None
    if measure == "mixer_share":
        if not ran or not ran["seconds"] or not any(seconds_of(name) for name in scopes):
            return None
        return 100.0 * (sum(seconds_of(name) for name in scopes) + seconds_of(staged)) / ran["seconds"]
    work = _traced_work(obs)
    if work is None:
        return None
    programs, rows, rewritten = work
    model, peak = obs["config"]["model"], peak_for(obs["device"]["kind"])
    if measure == "step_roofline":
        entry = (obs.get("scopes") or {}).get(scope)
        if not entry or not entry["seconds"] or not entry["runs"]:
            return None
        needed = flops.roofline_seconds(rewritten / flops_granite.ssm_row_state_bytes(model) * flops_granite.ssm_step_flops(model),
                                        flops_granite.ssm_step_bytes(rewritten), peak)
        obs.setdefault("notes", []).append(
            f"{scope}: {programs:.0f} state-space programs counted between the trace's edges, {rows / programs:.1f} rows a program, "
            f"{needed['bound']}-bound, {needed['seconds'] / programs * 1e6:.1f} us a program at the roofline; {entry['runs']:.0f} programs "
            f"traced, {entry['seconds'] / entry['runs'] * 1e6:.1f} us a program in the scope's operations and "
            f"{seconds_of(staged) / entry['runs'] * 1e6:.1f} us in the copies that stage the rows' states")
        return 100.0 * (needed["seconds"] / programs) * entry["runs"] / (entry["seconds"] + seconds_of(staged))
    if measure == "block_roofline":
        weights = (obs.get("param_bytes") or {}).get("ssm")
        if not ran or not ran["seconds"] or not ran["count"] or not weights:
            return None
        needed = flops.roofline_seconds(flops_granite.ssm_program_flops(rows, model),
                                        flops_granite.ssm_program_bytes(programs, rows, rewritten, weights, model), peak)
        obs.setdefault("notes", []).append(
            f"{program}: {programs:.0f} programs counted between the trace's edges, {rows / programs:.1f} rows a program, {weights / 1e6:.1f} MB "
            f"of parameters as they lie and {2 * rewritten / programs / 1e6:.1f} MB of states in and out a program, {needed['bound']}-bound, "
            f"{needed['seconds'] / programs * 1e6:.1f} us a program at the roofline; {ran['count']:.0f} programs traced, "
            f"{ran['seconds'] / ran['count'] * 1e6:.1f} us a program on the device")
        return 100.0 * (needed["seconds"] / programs) / (ran["seconds"] / ran["count"])
    raise ValueError(f"unknown measure {measure!r}")

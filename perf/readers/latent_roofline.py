"""The share of its roofline that the absorbed attention over latent caches reaches in the
batched decode programs, with the work and the kernel time taken from the SAME seconds:
the runner reads the program's counters when the trace goes on and when it goes off
(`counters_traced`), so the positions attended (`hivemind_moe_latent_positions_attended_total`,
`path=batched`: this kernel's work grows with the context, and the model's sizes alone do
not give it) and the rows that attended them (`hivemind_moe_decode_steps_total`,
`path=batched`: a row a block, every block of the cell's span keeps a latent cache) are
those of the traced programs. The least time for them (`perf/flops_mla.py`), per program
counted (`hivemind_moe_decode_calls_total`; a counter lags its program by at most a cohort,
at both edges), times the programs in the trace that hold the scope, over the device time
of the scope's operations there (`scopes`: the runner attributes a program's operations to
the named scope their `op_name` lies in) AND of ``staged``, where the runner gives it: the
compiler's own asynchronous copies of the rows' arrays into on-chip memory, which are where
the latents are read from HBM on a chip whose compiler stages them (the v5e: the scope's
operations then read on-chip memory and alone would read over 100 % at long contexts). Those
copies carry the array out again as well, so with them the share is a lower bound. A runner
that gives no `scopes` or does not read the counters at the trace's edges, a program without
the counter or a trace without the scope's operations gives nothing."""

from perf import flops, flops_mla
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta

ATTENDED = "hivemind_moe_latent_positions_attended_total"


def read(obs, scope, staged=None):
    entry = (obs.get("scopes") or {}).get(scope)
    edges = obs.get("counters_traced")
    if not entry or not entry["seconds"] or not entry["runs"] or not edges or ATTENDED not in edges["after"]:
        return None
    traced = {"counters": edges}
    positions = delta(traced, {"metric": ATTENDED, "series": "path=batched"})
    programs, rows = (delta(traced, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"})
                      for name in ("calls", "steps"))
    if not programs or not positions:
        return None
    model = obs["config"]["model"]
    needed = flops.roofline_seconds(flops_mla.latent_attend_flops(positions, model),
                                    flops_mla.latent_attend_bytes(positions, rows, model), peak_for(obs["device"]["kind"]))
    staging = ((obs.get("scopes") or {}).get(staged) or {}).get("seconds", 0.0)
    obs.setdefault("notes", []).append(
        f"{scope}: {programs:.0f} programs counted between the trace's edges, {rows / programs:.1f} rows a program at "
        f"{positions / rows:.0f} positions a row, {needed['bound']}-bound, {needed['seconds'] / programs * 1e6:.1f} us a program at the "
        f"roofline; {entry['runs']:.0f} programs traced, {entry['seconds'] / entry['runs'] * 1e6:.1f} us a program in the scope's "
        f"operations and {staging / entry['runs'] * 1e6:.1f} us in the copies that stage the rows' arrays")
    return 100.0 * (needed["seconds"] / programs) * entry["runs"] / (entry["seconds"] + staging)

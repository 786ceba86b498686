"""The share of its roofline that the batched decode program of a looped model's block reaches, with the
work and the program's time taken from the SAME seconds: the runner reads the program's counters when the
trace goes on and when it goes off (`counters_traced`), so the batched programs run
(`hivemind_moe_decode_calls_total`, `path=batched`), the live rows they held (`hivemind_moe_decode_steps_total`,
`path=batched`) and the positions those rows attended in the caches of their own passes
(`hivemind_moe_looped_positions_attended_total`, `path=batched`: this work grows with the context, and the
model's sizes alone do not give it) are those of the traced programs. The least time for them
(`perf/flops_ouro.py`: one block's float32 weights a program, 8 KB an attended position, the rows' hidden
states in and out; the FLOPs beside them), per program counted (a counter lags its program by at most a
cohort, at both edges), over the mean device time of the traced programs of that name (`programs`: the
profiler's XLA Modules line, summed by the runner). The bytes are a LOWER bound of what the program reads,
so the share cannot honestly pass 100 %. A runner that sums no programs or does not read the counters at the
trace's edges, a program without the counter (a parent commit) or a trace without the program gives nothing."""

from perf import flops, flops_ouro
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta

ATTENDED = "hivemind_moe_looped_positions_attended_total"


def read(obs, program):
    entry = (obs.get("programs") or {}).get(program)
    edges = obs.get("counters_traced")
    if not entry or not entry["seconds"] or not entry["count"] or not edges or ATTENDED not in edges["after"]:
        return None
    traced = {"counters": edges}
    positions = delta(traced, {"metric": ATTENDED, "series": "path=batched"})
    programs, rows = (delta(traced, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"})
                      for name in ("calls", "steps"))
    if not programs or not positions:
        return None
    model = obs["config"]["model"]
    needed = flops.roofline_seconds(flops_ouro.step_flops(rows, positions, model), flops_ouro.step_bytes(programs, rows, positions, model),
                                    peak_for(obs["device"]["kind"]))
    obs.setdefault("notes", []).append(
        f"{program}: {programs:.0f} programs counted between the trace's edges, {rows / programs:.1f} rows a program at "
        f"{positions / rows:.0f} positions a row, {needed['bound']}-bound, {needed['seconds'] / programs * 1e6:.1f} us a program at the "
        f"roofline; {entry['count']:.0f} programs traced, {entry['seconds'] / entry['count'] * 1e6:.1f} us a program on the device")
    return 100.0 * (needed["seconds"] / programs) / (entry["seconds"] / entry["count"])

"""The share of its roofline that a named scope of the batched decode programs reaches:
the least time for the scope's work (`perf/flops_sala.py`, per row, times the live rows
a batched program carried in the window — `hivemind_moe_decode_steps_total` over
`hivemind_moe_decode_calls_total`, `path=batched` — times the programs in the trace that
hold the scope) over the device time of the scope's operations in the trace (`scopes`
in the observations: the runner attributes a program's operations to the named scope
their `op_name` lies in, read off the compiled program's text). A runner that gives no
`scopes`, a program without the scope, or a trace without its operations gives nothing."""

from perf import flops, flops_sala
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta


def read(obs, scope, work):
    entry = (obs.get("scopes") or {}).get(scope)
    if not entry or not entry["seconds"] or not entry["runs"] or "counters" not in obs:
        return None
    programs, rows = (delta(obs, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"})
                      for name in ("calls", "steps"))
    if not programs:
        return None
    model = obs["config"]["model"]
    needed = flops.roofline_seconds(getattr(flops_sala, f"{work}_flops")(model), getattr(flops_sala, f"{work}_bytes")(model),
                                    peak_for(obs["device"]["kind"]))
    obs.setdefault("notes", []).append(
        f"{scope}: {needed['bound']}-bound, {needed['seconds'] * 1e6:.2f} us a row at the roofline, {rows / programs:.1f} rows a "
        f"program, {entry['runs']:.0f} programs traced, {entry['seconds'] / entry['runs'] * 1e6:.1f} us a program measured")
    return 100.0 * needed["seconds"] * (rows / programs) * entry["runs"] / entry["seconds"]

"""The expert computation's share of its roofline: the least time the chip could
take for the expert layers of the traced seconds over the device time of the
grouped-matmul operations in the trace.

The least time comes from the window's routing counters (`hivemind_moe_*_total`, every
serving path): per expert-layer call, the weights of the experts actually hit (at the
size the program reads them: `weight_itemsize`) plus the routed pairs' activations, and
the routed pairs' FLOPs (`perf/flops_moe.py`); the larger of bytes / bandwidth and
FLOPs / peak, taken on each path's totals (never more than the sum over its calls).
The calls inside the traced seconds are counted in the trace itself (events matching
`call_pattern` / `events_per_call`). A program without these counters (a parent commit)
or a trace without the operations gives nothing."""

from perf import flops, flops_moe
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta
from perf.trace_reduce import ops_matching

PATHS = ("batched", "direct", "pool")


def read(obs, pattern, call_pattern, events_per_call=1, weight_itemsize=4, activation_itemsize=4):
    if not obs.get("trace") or not obs["trace"]["devices"] or "counters" not in obs:
        return None
    kernel = ops_matching(obs["trace"]["ops"], pattern)
    calls_traced = ops_matching(obs["trace"]["ops"], call_pattern)["count"] / obs["trace"]["devices"] / events_per_call
    model = obs["config"]["model"]
    hidden, width = model["hidden_size"], model["intermediate_size"]
    peak = peak_for(obs["device"]["kind"])
    least, calls = 0.0, 0.0
    for path in PATHS:
        path_calls, pairs, hit = (delta(obs, {"metric": f"hivemind_moe_{name}_total", "series": f"path={path}"})
                                  for name in ("expert_layer_calls", "routed_pairs", "experts_hit"))
        if not path_calls:
            continue
        needed = flops.roofline_seconds(
            flops_moe.expert_layer_flops(pairs, hidden, width),
            flops_moe.expert_layer_bytes(hit, pairs, hidden, width, weight_itemsize, activation_itemsize), peak)
        obs.setdefault("notes", []).append(
            f"expert layers, {path}: {path_calls:.0f} calls in the window, {needed['bound']}-bound, "
            f"{needed['seconds'] / path_calls * 1e6:.1f} us a call at the roofline")
        least, calls = least + needed["seconds"], calls + path_calls
    if not kernel["seconds"] or not calls_traced or not calls:
        return None
    return 100.0 * (least / calls) * calls_traced / kernel["seconds"]

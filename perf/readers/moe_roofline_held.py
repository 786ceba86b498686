"""The share of its roofline that the grouped matmul reaches in an expert layer that
holds a SHARE of the experts it routes over, with the work and the kernel time taken
from the SAME seconds: the runner reads the program's counters when the trace goes on
and when it goes off (`counters_traced`), so the held pairs computed
(`hivemind_moe_held_pairs_total`) and the held experts they hit
(`hivemind_moe_experts_hit_total`, which counts held experts only where a share is
held) are those of the traced calls, not the window's mean call scaled to them. The
least time is taken at the width the configuration names under `width_key` (the routed
experts' own; `intermediate_size` may be a dense block's), per call on each path, times
the calls in the trace (events matching `call_pattern` / `events_per_call`; a counter
lags its program by at most a cohort, at both edges). Pairs routed to experts held
elsewhere are no work of this chip and are in neither count. A runner that does not
read the counters at the trace's edges, a program without the held-pairs counter or a
trace without the operations gives nothing."""

from perf import flops, flops_moe
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta
from perf.trace_reduce import ops_matching

PATHS = ("batched", "direct", "pool")


def read(obs, pattern, call_pattern, width_key, events_per_call=1, weight_itemsize=4, activation_itemsize=4):
    edges = obs.get("counters_traced")
    if not obs.get("trace") or not obs["trace"]["devices"] or not edges:
        return None
    if "hivemind_moe_held_pairs_total" not in edges["after"]:
        return None
    traced = {"counters": edges}
    kernel = ops_matching(obs["trace"]["ops"], pattern)
    calls_traced = ops_matching(obs["trace"]["ops"], call_pattern)["count"] / obs["trace"]["devices"] / events_per_call
    model = obs["config"]["model"]
    hidden, width = model["hidden_size"], model[width_key]
    peak = peak_for(obs["device"]["kind"])
    least, calls = 0.0, 0.0
    for path in PATHS:
        path_calls, pairs, hit = (delta(traced, {"metric": f"hivemind_moe_{name}_total", "series": f"path={path}"})
                                  for name in ("expert_layer_calls", "held_pairs", "experts_hit"))
        if not path_calls:
            continue
        needed = flops.roofline_seconds(
            flops_moe.expert_layer_flops(pairs, hidden, width),
            flops_moe.expert_layer_bytes(hit, pairs, hidden, width, weight_itemsize, activation_itemsize), peak)
        obs.setdefault("notes", []).append(
            f"held experts, {path}, traced seconds: {path_calls:.0f} calls counted, {pairs / path_calls:.1f} held pairs on "
            f"{hit / path_calls:.1f} held experts a call, {needed['bound']}-bound, "
            f"{needed['seconds'] / path_calls * 1e6:.1f} us a call at the roofline")
        least, calls = least + needed["seconds"], calls + path_calls
    if not kernel["seconds"] or not calls_traced or not calls:
        return None
    return 100.0 * (least / calls) * calls_traced / kernel["seconds"]

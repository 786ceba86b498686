"""`moe_roofline_held` for experts that work in a LATENT narrower than the hidden size and
have no gate: the share of its roofline that the grouped matmul reaches in an expert layer
that holds a share of the experts it routes over, with the held pairs computed
(`hivemind_moe_held_pairs_total`) and the held experts they hit
(`hivemind_moe_experts_hit_total`) read between the trace's edges (`counters_traced`). The
least time is taken at the experts' own input width (`moe_latent_size`) and width
(`moe_intermediate_size`), TWO grouped matmuls a call (`perf/flops_nemotron.py`), per call on
each path, times the calls in the trace (events matching `call_pattern` / `events_per_call`).
A runner that does not read the counters at the trace's edges, a program without the
held-pairs counter or a trace without the operations gives nothing."""

from perf import flops, flops_nemotron
from perf.peaks import peak_for
from perf.readers.counter_ratio import delta
from perf.readers.moe_roofline_held import PATHS
from perf.trace_reduce import ops_matching


def read(obs, pattern, call_pattern, events_per_call=2, weight_itemsize=4, activation_itemsize=4):
    edges = obs.get("counters_traced")
    if not obs.get("trace") or not obs["trace"]["devices"] or not edges or "hivemind_moe_held_pairs_total" not in edges["after"]:
        return None
    model = obs["config"]["model"]
    if "moe_latent_size" not in model:
        return None
    traced = {"counters": edges}
    kernel = ops_matching(obs["trace"]["ops"], pattern)
    calls_traced = ops_matching(obs["trace"]["ops"], call_pattern)["count"] / obs["trace"]["devices"] / events_per_call
    peak = peak_for(obs["device"]["kind"])
    least, calls = 0.0, 0.0
    for path in PATHS:
        path_calls, pairs, hit = (delta(traced, {"metric": f"hivemind_moe_{name}_total", "series": f"path={path}"})
                                  for name in ("expert_layer_calls", "held_pairs", "experts_hit"))
        if not path_calls:
            continue
        needed = flops.roofline_seconds(flops_nemotron.latent_expert_layer_flops(pairs, model),
                                        flops_nemotron.latent_expert_layer_bytes(hit, pairs, model, weight_itemsize, activation_itemsize), peak)
        obs.setdefault("notes", []).append(
            f"held experts in the latent, {path}, traced seconds: {path_calls:.0f} calls counted, {pairs / path_calls:.1f} held pairs on "
            f"{hit / path_calls:.1f} held experts a call, {needed['bound']}-bound, {needed['seconds'] / path_calls * 1e6:.1f} us a call at the roofline")
        least, calls = least + needed["seconds"], calls + path_calls
    if not kernel["seconds"] or not calls_traced or not calls:
        return None
    return 100.0 * (least / calls) * calls_traced / kernel["seconds"]

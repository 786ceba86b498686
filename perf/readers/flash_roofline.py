"""The flash kernels' share of their roofline: the least time the chip could take
for the attention of the traced steps (forward + backward FLOPs and bytes from the
configuration's shapes, `perf/flops.py`; the larger of FLOPs / peak and bytes /
bandwidth) over the device time of the kernels in the trace. The bound that binds
is noted in the run's log."""

from perf import flops
from perf.peaks import peak_for
from perf.readers.trace_ms_per_step import steps_traced
from perf.trace_reduce import ops_matching


def read(obs, pattern, step_pattern=None, events_per_step=1, events_per_step_from_config=None, itemsize=2):
    if not obs.get("trace") or not obs["trace"]["devices"]:
        return None
    kernel_s = ops_matching(obs["trace"]["ops"], pattern)["seconds"]
    steps = steps_traced(obs, step_pattern, events_per_step, events_per_step_from_config)
    if not kernel_s or not steps:
        return None
    model, recipe = obs["config"]["model"], obs["config"]["recipe"]
    heads = model["num_attention_heads"]
    shape = dict(batch=recipe["sequences_per_peer_per_step"] // obs["trace"]["devices"] or 1, heads=heads,
                 q_len=recipe["seq_len"], kv_len=recipe["seq_len"], head_dim=model["hidden_size"] // heads)
    peak = peak_for(obs["device"]["kind"])
    least = 0.0
    for backward in (False, True):
        needed = flops.roofline_seconds(
            flops.attention_flops(causal=False, backward=backward, **shape),
            flops.attention_bytes(kv_heads=heads, itemsize=itemsize, backward=backward, **shape), peak)
        obs.setdefault("notes", []).append(
            f"flash {'backward' if backward else 'forward'} per layer call: {needed['bound']}-bound, "
            f"{needed['seconds'] * 1e6:.1f} us at the roofline")
        least += needed["seconds"] * model["num_hidden_layers"]
    return 100.0 * least * steps / kernel_s

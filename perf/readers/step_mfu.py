"""Model-FLOP/s utilisation: FLOPs the forward and backward passes need per token
(`perf/flops.py`, from the configuration's sizes) x tokens per second, over chips x
the published bf16 peak (`perf/peaks.json`). Recomputed work does not count."""

from perf import flops
from perf.peaks import peak_for


def read(obs, count="tokens"):
    done = obs.get("counts", {}).get(count)
    if not done or not obs.get("window_s"):
        return None
    recipe = obs["config"]["recipe"]
    per_token = flops.albert_flops_per_token(obs["config"]["model"], recipe["seq_len"], recipe["masked_loss_fraction"])
    peak = peak_for(obs["device"]["kind"])["bf16_flops"]
    return 100.0 * per_token * done / obs["window_s"] / (obs["chips"] * peak)

"""RemoteMixtureOfExperts: route each input to its top-k experts across the swarm and
mix their outputs (capability parity: reference hivemind/moe/client/moe.py:25-442).

Host-orchestrated gating, device-vectorized mixing: expert fan-out happens through
ONE batched RemoteCallMany primitive (concurrent RPCs, alive-mask fault tolerance —
reference _RemoteCallMany) and the mixture itself is a single masked-softmax einsum
over [batch, k] slots, not per-sample Python loops. ``k_min``/``backward_k_min``
bound how many experts must answer per sample; ``timeout_after_k_min`` caps how long
stragglers are awaited once enough answered."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hivemind_tpu.dht import DHT
from hivemind_tpu.moe.client.beam_search import MoEBeamSearcher
from hivemind_tpu.moe.client.call_many import EXPERT_BREAKERS, RemoteCallMany
from hivemind_tpu.moe.client.expert import RemoteExpert
from hivemind_tpu.moe.expert_uid import ExpertInfo
from hivemind_tpu.p2p import P2P
from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class RemoteMixtureOfExperts:
    """:param grid_size: experts live on a grid of this shape under uid_prefix
    :param k_best: experts per sample
    :param k_min: minimum experts that must respond (reference k_min semantics)
    :param backward_k_min: minimum experts whose backward must succeed per sample
    :param timeout_after_k_min: extra seconds granted to stragglers once every
        sample has k_min responses (reference moe.py:41-44)"""

    def __init__(
        self,
        *,
        dht: DHT,
        in_features: int,
        grid_size: Sequence[int],
        uid_prefix: str,
        k_best: int = 4,
        k_min: int = 1,
        backward_k_min: int = 1,
        forward_timeout: Optional[float] = None,
        backward_timeout: Optional[float] = None,
        timeout_after_k_min: Optional[float] = None,
        beam_size: Optional[int] = None,
        seed: int = 0,
    ):
        self.dht = dht
        from hivemind_tpu.utils.loop import get_loop_runner

        self.p2p: P2P = get_loop_runner().run_coroutine(dht.replicate_p2p())
        self.grid_size = tuple(grid_size)
        self.k_best, self.k_min, self.backward_k_min = k_best, k_min, backward_k_min
        self.forward_timeout, self.backward_timeout = forward_timeout, backward_timeout
        self.timeout_after_k_min = timeout_after_k_min
        self.beam_size = beam_size if beam_size is not None else k_best * 2
        self.beam_searcher = MoEBeamSearcher(dht, uid_prefix, grid_size)
        rng = np.random.RandomState(seed)
        # the trainable gating projection (reference: nn.Linear at moe.py:74)
        self.proj = jnp.asarray(rng.randn(in_features, sum(grid_size)) * 0.01, jnp.float32)
        self._experts: Dict[str, RemoteExpert] = {}

    def _get_expert(self, info: ExpertInfo) -> RemoteExpert:
        expert = self._experts.get(info.uid)
        if expert is None:
            expert = self._experts[info.uid] = RemoteExpert(info, self.p2p)
        elif expert.expert_info != info:
            expert.update_info(info)  # replica set / primary may have moved
        return expert

    def expert_scorecards(self) -> Dict[str, dict]:
        """This client's serving scorecards (ISSUE 9) for the experts this
        mixture has called: success rate, latency quantiles, timeouts, sheds —
        the caller-side view that rides the DHT telemetry snapshot."""
        from hivemind_tpu.telemetry.serving import SCORECARDS

        cards = SCORECARDS.export()
        return {uid: cards[uid] for uid in self._experts if uid in cards}

    def _split_scores(self, flat_scores: jax.Array) -> List[jax.Array]:
        out, offset = [], 0
        for size in self.grid_size:
            out.append(flat_scores[:, offset : offset + size])
            offset += size
        return out

    def _uid_coords(self, uid: str) -> List[int]:
        """Grid coordinates = the part of the uid after the grid prefix (the prefix
        itself may contain numeric components, e.g. per-layer grids 'ffn.3.')."""
        prefix = self.beam_searcher.uid_prefix
        assert uid.startswith(prefix), (uid, prefix)
        return [int(c) for c in uid[len(prefix):].split(".")]

    def __call__(self, x: jax.Array, proj: Optional[jax.Array] = None) -> jax.Array:
        """x: [batch, in_features]. Returns the expert mixture [batch, out_features].
        Eager-mode API (expert selection is data-dependent host orchestration)."""
        proj = proj if proj is not None else self.proj
        grid_scores = self._split_scores(x @ proj)
        chosen = self.beam_searcher.batch_find_best_experts(
            [np.asarray(jax.lax.stop_gradient(s)) for s in grid_scores], self.beam_size
        )
        return self._mix(x, grid_scores, chosen)

    def _mix(self, x: jax.Array, grid_scores: List[jax.Array], chosen: List[List[ExpertInfo]]) -> jax.Array:
        batch_size = x.shape[0]
        # breaker-aware routing (resilience/breaker.py): experts whose circuit is
        # hard-open are demoted below every live candidate, so a dead expert does
        # not burn one of a sample's k_best slots while healthy ones rank lower.
        # `in EXPERT_BREAKERS` is a pure read; half-open probes happen in _fan_out.
        sample_experts = []
        for sample in range(batch_size):
            candidates = chosen[sample]
            live = [info for info in candidates if info.uid not in EXPERT_BREAKERS]
            banned = [info for info in candidates if info.uid in EXPERT_BREAKERS]
            sample_experts.append((live + banned)[: self.k_best])
        if not any(sample_experts):
            raise RuntimeError("beam search found no experts; is any server declared on this grid?")
        k = max(len(infos) for infos in sample_experts)

        # one batched, concurrent, fault-tolerant fan-out for the whole batch
        rows = [
            [self._get_expert(info) for info in infos] + [None] * (k - len(infos))
            for infos in sample_experts
        ]
        call_many = RemoteCallMany(
            rows,
            k_min=self.k_min,
            backward_k_min=self.backward_k_min,
            forward_timeout=self.forward_timeout,
            backward_timeout=self.backward_timeout,
            timeout_after_k_min=self.timeout_after_k_min,
        )
        outputs, alive = call_many(x)  # [batch, k, d_out], [batch, k]

        # vectorized gating: logit[b, slot] = sum_d grid_scores[d][b, coord_d]
        ndim = len(self.grid_size)
        coords = np.zeros((batch_size, k, ndim), np.int32)
        valid = np.zeros((batch_size, k), bool)
        for sample, infos in enumerate(sample_experts):
            for slot, info in enumerate(infos):
                coords[sample, slot] = self._uid_coords(info.uid)
                valid[sample, slot] = True
        rows_index = jnp.arange(batch_size)[:, None]
        logits = sum(
            grid_scores[dim][rows_index, jnp.asarray(coords[:, :, dim])] for dim in range(ndim)
        )
        mask = jnp.asarray(valid) & alive
        logits = jnp.where(mask, logits, -1e9)  # finite: -inf NaNs the softmax grad
        weights = jax.nn.softmax(logits, axis=-1)
        weights = jnp.where(mask, weights, 0.0)  # dead slots contribute exactly zero
        return jnp.einsum("bk,bkd->bd", weights, outputs)


class RemoteSwitchMixtureOfExperts(RemoteMixtureOfExperts):
    """Switch-Transformer routing: top-1 expert, multiplicative jitter on inputs to
    the gate, grid dropout for load spreading, and a utilization EMA for
    load-balancing diagnostics (capability parity: reference
    hivemind/moe/client/switch_moe.py:17-225).

    :param grid_dropout: keep-probability per grid COORDINATE per call; dropped
        coordinates get -inf gating score so no sample routes to them this batch,
        forcing exploration across the grid (reference switch_moe.py:46,84-98).
        1.0 disables dropout."""

    def __init__(
        self,
        *,
        jitter_eps: float = 1e-2,
        utilization_alpha: float = 0.01,
        grid_dropout: float = 1.0,
        **kwargs,
    ):
        kwargs.setdefault("k_best", 1)
        # reference switch defaults (switch_moe.py:49-51): a token whose expert
        # fails contributes ZEROS instead of failing the whole batch
        kwargs.setdefault("k_min", 0)
        kwargs.setdefault("backward_k_min", 0)
        super().__init__(**kwargs)
        self.jitter_eps = jitter_eps
        self.utilization_alpha = utilization_alpha
        self.grid_dropout = grid_dropout
        self.grid_utilization = [np.full(size, 1.0 / size, np.float64) for size in self.grid_size]
        self._jitter_rng = np.random.RandomState(self.beam_size)

    def __call__(self, x: jax.Array, proj: Optional[jax.Array] = None) -> jax.Array:
        # jitter perturbs the GATING scores only; experts see the original input and
        # only ONE beam search runs (reference switch_moe.py:78-79,126)
        noise = self._jitter_rng.uniform(
            1 - self.jitter_eps, 1 + self.jitter_eps, size=(x.shape[0], 1)
        ).astype(np.float32)
        proj = proj if proj is not None else self.proj
        grid_scores = self._split_scores((x * jnp.asarray(noise)) @ proj)
        if self.grid_dropout < 1.0:
            keep_masks = [
                self._jitter_rng.rand(size) < self.grid_dropout for size in self.grid_size
            ]
            for dim, mask in enumerate(keep_masks):
                if not mask.any():
                    # never drop a whole dimension (that would un-restrict routing
                    # to arbitrary tie-breaks among -1e9 scores): keep the
                    # coordinate the gate likes best on this batch
                    best = int(np.argmax(np.asarray(jnp.mean(grid_scores[dim], axis=0))))
                    mask[best] = True
            grid_scores = [
                jnp.where(jnp.asarray(mask)[None, :], score, -1e9)
                for score, mask in zip(grid_scores, keep_masks)
            ]
        chosen = self.beam_searcher.batch_find_best_experts(
            [np.asarray(jax.lax.stop_gradient(s)) for s in grid_scores], self.beam_size
        )
        self._update_utilization(chosen)
        return self._mix(x, grid_scores, chosen)

    def _update_utilization(self, chosen: List[List[ExpertInfo]]) -> None:
        alpha = self.utilization_alpha
        for sample_infos in chosen:
            for info in sample_infos[:1]:  # top-1 routing
                for dim, coord in enumerate(self._uid_coords(info.uid)):
                    self.grid_utilization[dim] *= 1 - alpha
                    self.grid_utilization[dim][coord] += alpha

"""Load real (sharded) Llama-family checkpoints into ``llama_block`` serving
backends — the Petals-style block server of BASELINE config #5 (the reference has
no checkpoint loader of its own; Petals, its downstream, loads HF checkpoints into
per-layer block servers the same way).

- **Checkpoint format**: HuggingFace layout — ``config.json`` plus either a single
  ``model.safetensors`` or a sharded set with ``model.safetensors.index.json``.
  Tensors are read lazily per block (one decoder layer at a time), so host memory
  stays ~one block, never the whole model.
- **Weight mapping**: HF ``model.layers.N.self_attn.{q,k,v,o}_proj.weight`` /
  ``mlp.{gate,up,down}_proj.weight`` / ``{input,post_attention}_layernorm.weight``
  map onto :class:`LlamaBlockExpert`'s flax tree (Dense kernels transposed: HF
  stores [out, in]). HF's rotary convention (contiguous-half rotate) matches
  ``apply_rope``, so outputs agree with the original model.
- **Int8 serving**: pass ``weight_quantization="int8"`` to store blocks with the
  repo's blockwise absmax codec (4x less resident HBM; see ops/quantized_params).
- **HBM budgeting**: :func:`plan_block_capacity` decides how many blocks fit one
  chip from measured per-block bytes + decode-session KV budget + headroom.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclass
class LlamaCheckpointConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    num_hidden_layers: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6  # HF LlamaConfig default; Llama-2 ships 1e-5

    @classmethod
    def load(cls, checkpoint_dir) -> "LlamaCheckpointConfig":
        with open(Path(checkpoint_dir) / "config.json") as f:
            raw = json.load(f)
        return cls(
            hidden_size=int(raw["hidden_size"]),
            num_attention_heads=int(raw["num_attention_heads"]),
            num_key_value_heads=int(raw.get("num_key_value_heads", raw["num_attention_heads"])),
            intermediate_size=int(raw["intermediate_size"]),
            num_hidden_layers=int(raw["num_hidden_layers"]),
            rope_theta=float(raw.get("rope_theta", 10000.0)),
            rms_norm_eps=float(raw.get("rms_norm_eps", 1e-6)),
        )


class ShardedSafetensorsReader:
    """Lazy tensor access over a single- or multi-file safetensors checkpoint."""

    def __init__(self, checkpoint_dir):
        self.dir = Path(checkpoint_dir)
        index_path = self.dir / "model.safetensors.index.json"
        if index_path.exists():
            with open(index_path) as f:
                self.weight_map: Dict[str, str] = json.load(f)["weight_map"]
        else:
            single = self.dir / "model.safetensors"
            if not single.exists():
                raise FileNotFoundError(
                    f"{self.dir} holds neither model.safetensors nor an index"
                )
            from safetensors import safe_open

            with safe_open(single, framework="np") as f:
                self.weight_map = {name: "model.safetensors" for name in f.keys()}
        self._open_files: dict = {}

    def names(self) -> Iterable[str]:
        return self.weight_map.keys()

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        try:
            filename = self.weight_map[name]
        except KeyError:
            raise KeyError(f"checkpoint has no tensor {name!r}") from None
        handle = self._open_files.get(filename)
        if handle is None:
            handle = self._open_files[filename] = safe_open(
                self.dir / filename, framework="np"
            )
        return np.asarray(handle.get_tensor(name))


def _block_params_from_hf(reader: ShardedSafetensorsReader, layer: int) -> dict:
    """One decoder layer's HF tensors as a LlamaBlockExpert flax param tree."""
    prefix = f"model.layers.{layer}."

    def kernel(hf_name: str) -> dict:
        # HF Linear stores [out_features, in_features]; flax Dense wants [in, out]
        return {"kernel": np.ascontiguousarray(reader.get(prefix + hf_name).T.astype(np.float32))}

    return {
        "query": kernel("self_attn.q_proj.weight"),
        "key": kernel("self_attn.k_proj.weight"),
        "value": kernel("self_attn.v_proj.weight"),
        "attention_out": kernel("self_attn.o_proj.weight"),
        "ffn_gate": kernel("mlp.gate_proj.weight"),
        "ffn_up": kernel("mlp.up_proj.weight"),
        "ffn_down": kernel("mlp.down_proj.weight"),
        "attention_norm": {"scale": reader.get(prefix + "input_layernorm.weight").astype(np.float32)},
        "ffn_norm": {"scale": reader.get(prefix + "post_attention_layernorm.weight").astype(np.float32)},
    }


def load_llama_blocks(
    checkpoint_dir,
    *,
    layers: Optional[Sequence[int]] = None,
    uid_prefix: str = "llama.",
    weight_quantization: Optional[str] = None,
    max_batch_size: int = 64,
    optimizer=None,
    mesh=None,
    shard_axis: str = "tp",
) -> Tuple[Dict[str, "object"], LlamaCheckpointConfig]:
    """Build ``{uid: ModuleBackend}`` serving the checkpoint's decoder layers.

    ``layers`` defaults to all of them; uid = ``f"{uid_prefix}{layer}"`` so a
    ``RemoteSequential(dht, uid_prefix, n)`` client chains them in order. Blocks
    are loaded one at a time (host memory ~= one block). With ``mesh``, each
    block becomes a :class:`MeshModuleBackend` — params and KV caches sharded
    over ``shard_axis``, for blocks one chip cannot hold.
    """
    import optax

    from hivemind_tpu.moe.server.layers import name_to_block
    from hivemind_tpu.moe.server.mesh_backend import MeshModuleBackend
    from hivemind_tpu.moe.server.module_backend import ModuleBackend

    config = LlamaCheckpointConfig.load(checkpoint_dir)
    reader = ShardedSafetensorsReader(checkpoint_dir)
    layers = list(layers) if layers is not None else list(range(config.num_hidden_layers))

    backends: Dict[str, ModuleBackend] = {}
    for layer in layers:
        module = name_to_block["llama_block"](
            config.hidden_size,
            num_heads=config.num_attention_heads,
            num_kv_heads=config.num_key_value_heads,
            rope_theta=config.rope_theta,
            ffn_inner=config.intermediate_size,
            rms_eps=config.rms_norm_eps,
            mesh=mesh,
        )
        common_opts = dict(
            optimizer=optimizer or optax.sgd(0.0),
            sample_input=np.zeros((2, 8, config.hidden_size), np.float32),
            max_batch_size=max_batch_size,
            weight_quantization=weight_quantization,
        )
        if mesh is not None:
            backend = MeshModuleBackend(
                f"{uid_prefix}{layer}", module, mesh=mesh, shard_axis=shard_axis, **common_opts
            )
        else:
            backend = ModuleBackend(f"{uid_prefix}{layer}", module, **common_opts)
        backend.load_params(_block_params_from_hf(reader, layer))
        backends[backend.name] = backend
        logger.info(
            f"loaded block {layer} as {backend.name!r} "
            f"({backend.param_bytes() / 1e6:.1f} MB resident"
            f"{', int8' if weight_quantization else ''})"
        )
    return backends, config


# ---------------------------------------------------------------- HBM budgeting


def predict_block_param_bytes(
    config: LlamaCheckpointConfig, weight_quantization: Optional[str] = None
) -> int:
    """Resident bytes ONE decoder block should cost, from config arithmetic alone —
    the planning input for :func:`plan_block_capacity` BEFORE any weights load
    (VERDICT r3 #8: the prediction is asserted against measured bytes within 10%
    in tests/test_llama_loader.py). Exact model of the storage: fp32 kernels +
    norm scales, or blockwise int8 (codes padded to QUANT_BLOCK_SIZE + one fp32
    absmax per block; 1-D norm scales stay exact fp32)."""
    hid, inner = config.hidden_size, config.intermediate_size
    head_dim = hid // config.num_attention_heads
    kv = config.num_key_value_heads * head_dim
    matrices = [
        hid * hid,   # q_proj
        kv * hid,    # k_proj
        kv * hid,    # v_proj
        hid * hid,   # o_proj
        inner * hid,  # gate_proj
        inner * hid,  # up_proj
        hid * inner,  # down_proj
    ]
    norm_bytes = 2 * hid * 4  # input/post-attention RMSNorm scales, always fp32
    if weight_quantization == "int8":
        from hivemind_tpu.ops.quantized_params import QUANT_BLOCK_SIZE

        total = norm_bytes
        for size in matrices:
            blocks = -(-size // QUANT_BLOCK_SIZE)  # ceil
            total += blocks * QUANT_BLOCK_SIZE + blocks * 4  # int8 codes + fp32 absmax
        return total
    return sum(matrices) * 4 + norm_bytes


def decode_cache_bytes(config: LlamaCheckpointConfig, batch: int, max_len: int) -> int:
    """KV-cache bytes ONE session costs for ONE block (bf16 K + V at kv-heads width,
    ``[batch, kv_heads, max_len, head_dim]`` — see LlamaBlockExpert.init_decode_cache)."""
    head_dim = config.hidden_size // config.num_attention_heads
    return 2 * 2 * batch * max_len * config.num_key_value_heads * head_dim


def device_hbm_bytes(device=None) -> Optional[int]:
    """The accelerator's memory limit, when the platform reports one (TPU does;
    CPU jax does not — callers then pass an explicit budget)."""
    import jax

    device = device or jax.local_devices()[0]
    try:
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:
        pass
    return None


def plan_block_capacity(
    block_bytes: int,
    *,
    hbm_bytes: Optional[int] = None,
    device=None,
    decode_sessions: int = 0,
    cache_bytes_per_session_block: int = 0,
    reserve_fraction: float = 0.2,
    mesh_devices: int = 1,
) -> int:
    """How many blocks fit the serving unit:
    ``(HBM*devices*(1-reserve) - sessions*cache) / block``.

    ``mesh_devices`` > 1 plans a MESH-sharded server (``MeshModuleBackend``)
    from GLOBAL block bytes only — e.g. pre-load planning via
    ``predict_block_param_bytes`` — by assuming ideal ``1/mesh_devices``
    residency: ``hbm_bytes`` stays the PER-CHIP budget and the pooled budget
    scales with the mesh. When a probe block EXISTS, prefer passing its
    ``param_bytes_per_device()`` as ``block_bytes`` with the default
    ``mesh_devices=1`` instead (run_server does): measured residency also
    counts kernels that REPLICATE because their dims do not divide the mesh.
    Never combine per-device bytes with ``mesh_devices`` > 1 — that multiplies
    the budget while the cost is already divided, overcommitting ~N².

    ``reserve_fraction`` keeps headroom for activations, the transient dense
    weights of int8 serving, and XLA workspace. Returns at least 0.
    """
    if hbm_bytes is None:
        hbm_bytes = device_hbm_bytes(device)
    if hbm_bytes is None:
        raise ValueError(
            "platform does not report a memory limit; pass hbm_bytes explicitly"
        )
    usable = int(hbm_bytes * max(int(mesh_devices), 1) * (1.0 - reserve_fraction))
    per_block = block_bytes + decode_sessions * cache_bytes_per_session_block
    if per_block <= 0:
        return 0
    return max(usable // per_block, 0)


class LlamaClientHead:
    """The client-side ends of a Petals-style pipeline: token embedding in,
    final RMSNorm + LM head out (Petals keeps exactly these on the client while
    the decoder blocks run remotely). Loaded from the same HF checkpoint:
    ``model.embed_tokens.weight``, ``model.norm.weight``, and ``lm_head.weight``
    (absent ⇒ tied with the embedding, as Llama publishes it)."""

    def __init__(self, embed: np.ndarray, norm_scale: np.ndarray, lm_head: np.ndarray,
                 rms_eps: float = 1e-6):
        self.embed_matrix = embed  # [vocab, hid]
        self.norm_scale = norm_scale  # [hid]
        self.lm_head_matrix = lm_head  # [vocab, hid]
        self.rms_eps = rms_eps

    @classmethod
    def load(cls, checkpoint_dir) -> "LlamaClientHead":
        reader = ShardedSafetensorsReader(checkpoint_dir)
        config = LlamaCheckpointConfig.load(checkpoint_dir)
        embed = reader.get("model.embed_tokens.weight").astype(np.float32)
        norm = reader.get("model.norm.weight").astype(np.float32)
        try:
            lm_head = reader.get("lm_head.weight").astype(np.float32)
        except KeyError:
            lm_head = embed  # tied embeddings
        return cls(embed, norm, lm_head, rms_eps=config.rms_norm_eps)

    @property
    def vocab_size(self) -> int:
        return self.embed_matrix.shape[0]

    def embed(self, token_ids: np.ndarray) -> np.ndarray:
        """[batch, seq] int ids -> [batch, seq, hid] fp32 hidden states."""
        return self.embed_matrix[np.asarray(token_ids, np.int64)]

    def logits(self, hidden: np.ndarray) -> np.ndarray:
        """[batch, seq, hid] block-stack output -> [batch, seq, vocab] logits
        (RMSNorm then the LM projection, matching HF's LlamaForCausalLM tail)."""
        hidden = np.asarray(hidden, np.float32)
        rms = np.sqrt(np.mean(hidden**2, axis=-1, keepdims=True) + self.rms_eps)
        normed = hidden / rms * self.norm_scale
        return normed @ self.lm_head_matrix.T


def generate_greedy(
    head: LlamaClientHead,
    pipe,
    prompt_ids: np.ndarray,
    max_new_tokens: int,
    session_id: Optional[str] = None,
) -> np.ndarray:
    """Greedy decoding through a RemoteSequential block pipeline with KV-cache
    sessions: one prefill RPC chain, then one single-token chain per new token
    (the LAST token needs no trailing step — its cache entry would go unread).
    ``session_id`` defaults to a fresh unique id: the server keys sessions
    globally by (uid, session_id), so a shared constant would let concurrent
    generations silently clobber each other's KV caches.
    ``prompt_ids``: [batch, prompt_len]; returns [batch, prompt_len + new]."""
    import uuid

    if session_id is None:
        session_id = f"gen-{uuid.uuid4().hex}"
    prompt = np.asarray(prompt_ids, np.int64)
    # preallocate the full id buffer once: the old per-token
    # np.concatenate([ids, next_ids]) recopied the whole history every step,
    # making generation O(len²) in tokens (ISSUE 10 satellite)
    ids = np.empty((prompt.shape[0], prompt.shape[1] + max_new_tokens), np.int64)
    ids[:, : prompt.shape[1]] = prompt
    hidden = pipe.decode_step(head.embed(prompt), session_id, reset=True)
    for step in range(max_new_tokens):
        next_ids = np.argmax(head.logits(np.asarray(hidden)[:, -1:]), axis=-1)
        ids[:, prompt.shape[1] + step] = next_ids[:, 0]
        if step + 1 < max_new_tokens:
            hidden = pipe.decode_step(head.embed(next_ids), session_id)
    return ids

"""Compile for a described (not attached) TPU v5e, at published widths: the flash
kernels at the shapes the benchmark's cells feed them (ISSUE 33: a tile Mosaic
refuses fails here, not on the chip), and what the OLMoE
block adds to the decode path: the sparse expert layer and one batched decode step.
Nothing runs: this guards what the chip's compiler makes of the code (ISSUE 27) —
`jax.lax.ragged_dot` stays the compiler's own grouped-matmul kernel on float32
weights with no converted copy of the experts written to memory, and a batched step
with 4,096-slot caches fits the chip. Times and results come from chip runs only.

The topology is described inside a fixture (never at import: one process at a time may
load the TPU's library, and every xdist worker imports every test file)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HIDDEN, HEADS, EXPERTS, TOP_K, INNER, MAX_LEN = 2048, 16, 64, 8, 1024, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Such a compile is written to the persistent cache but cannot be read back
    without a chip; the next one would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tokens", [6, 32, 2048])
def test_expert_layer_compiles_to_the_native_grouped_matmul(one_chip, no_compile_cache, tokens):
    from hivemind_tpu.ops.sparse_experts import route_top_k, routed_swiglu

    def layer(x, router, w_gate, w_up, w_down):
        top_p, top_e = route_top_k(x, router, TOP_K)
        return routed_swiglu(x, top_p, top_e, w_gate, w_up, w_down)

    args = (_shape((tokens, HIDDEN), jnp.bfloat16, one_chip), _shape((HIDDEN, EXPERTS), jnp.float32, one_chip),
            _shape((EXPERTS, HIDDEN, INNER), jnp.float32, one_chip), _shape((EXPERTS, HIDDEN, INNER), jnp.float32, one_chip),
            _shape((EXPERTS, INNER, HIDDEN), jnp.float32, one_chip))
    compiled = jax.jit(layer).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 3, "the three grouped matmuls are not the compiler's ragged-dot kernel"
    # no bf16 (or any other) copy of an expert matrix among the temporaries: one is 268 MB in bf16
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * tokens * TOP_K * HIDDEN * 4 * 8 + 64 * 2**20


def _compiled_batched_step(module, hidden: int, max_len: int, rows: int, one_chip):
    """The program `DecodeSessionManager._batched_fn` itself builds for a bucket of ``rows``
    (no copy of its body here), over a backend that holds shapes only, compiled for the chip;
    beside it the shapes of one session's cache leaves."""
    from types import SimpleNamespace

    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

    on_chip = lambda tree: jax.tree_util.tree_map(lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    cache = on_chip(jax.eval_shape(lambda: module.init_decode_cache(1, max_len)))
    manager = DecodeSessionManager({"blk.0": SimpleNamespace(module=module, dense_params=lambda p: p)}, max_len=max_len)
    compiled = manager._batched_fn("blk.0", rows).jitted.lower(
        on_chip(params), _shape((rows, 1, hidden), jnp.float32, one_chip), tuple((leaf,) * rows for leaf in cache),  # leaf by leaf, the rows' arrays
        _shape((rows,), jnp.int32, one_chip)).compile()
    # every cache leaf of every row is aliased to an output (ISSUE 50: the program donates them), by the chip's
    # compiler's own account; a block whose step copied a cache argument first would come out short here
    cache_bytes = rows * sum(leaf.size * leaf.dtype.itemsize for leaf in cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes, (compiled.memory_analysis().alias_size_in_bytes, cache_bytes)
    return compiled, [leaf.shape for leaf in cache]


def _joined(text: str, rows: int, leaf_shape) -> int:
    """How often an array of the rows' caches JOINED along the batch axis appears in a compiled program."""
    return text.count("bf16[" + ",".join(map(str, (rows,) + tuple(leaf_shape[1:]))) + "]")


def test_batched_decode_step_of_eight_sessions_fits_the_chip(one_chip, no_compile_cache):
    """OLMoE's block at a bucket of 8 with 4,096-slot caches. Since ISSUE 42 the program steps on
    the rows' own caches (`decode_rows_apart`), and since ISSUE 50 in the donated arrays themselves:
    its outputs, 8 x 2 x 16.8 MB = 256 MiB, are all aliased to arguments (`_compiled_batched_step`
    holds that), so beside its arguments it needs 2.8 MiB of temporaries; while it joined the
    caches it held 386.5 MiB of temporaries beside them."""
    from hivemind_tpu.moe.server.layers import name_to_block

    module = name_to_block["olmoe_block"](HIDDEN, num_heads=HEADS, num_experts=EXPERTS, experts_per_token=TOP_K, expert_inner=INNER)
    compiled, leaves = _compiled_batched_step(module, HIDDEN, MAX_LEN, 8, one_chip)
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 3
    assert leaves == [(1, HEADS, MAX_LEN, HIDDEN // HEADS)] * 2  # one head's slots together (ISSUE 52), the bytes as before
    assert _joined(text, 8, leaves[0]) == 0, "an array of the joined caches' shape: the rows' caches are not stepped where they lie"
    memory = compiled.memory_analysis()
    # arguments: 1.68 GB of weights + 8 x 33.5 MB of caches
    assert memory.output_size_in_bytes < 257 * 2**20 and memory.temp_size_in_bytes < 16 * 2**20


def test_batched_mistral_step_of_sixteen_sessions_copies_no_cache_at_query_width(one_chip, no_compile_cache):
    """Mistral-7B's block (32 query heads on 8 key-value heads, float32 weights) at a bucket of 16 with 2,048-slot
    caches, 8.4 MB a session: since ISSUE 52 a row's step holds the four queries of a key-value head against that
    head's cache as it lies (`_grouped_cache_step`), so the chip's program holds no array of a cache's length at
    query width, in either order of its axes (2 x 16.8 MB a row a step while the key-value heads were repeated),
    none of the joined caches' shape, and 16 x 8.4 MB of outputs, all aliased to the donated arguments."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, heads, kv_heads, max_len, rows = 4096, 32, 8, 2048, 16
    module = name_to_block["llama_block"](hidden, num_heads=heads, num_kv_heads=kv_heads, ffn_inner=14336, rope_theta=1000000.0, rms_eps=1e-5)
    compiled, leaves = _compiled_batched_step(module, hidden, max_len, rows, one_chip)
    text = compiled.as_text()
    assert leaves == [(1, kv_heads, max_len, hidden // heads)] * 2 and _joined(text, rows, leaves[0]) == 0
    at_query_width = [f"[1,{heads},{max_len},{hidden // heads}]", f"[1,{max_len},{heads},{hidden // heads}]",
                      f"[1,{kv_heads},{heads // kv_heads},{max_len},{hidden // heads}]", f"[1,{max_len},{kv_heads},{heads // kv_heads},{hidden // heads}]"]
    assert not [shape for shape in at_query_width if shape in text], "a row's keys or values were copied at query width"
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 135 * 2**20 and memory.temp_size_in_bytes < 16 * 2**20


def test_batched_full_attention_step_of_sixteen_sessions_fits_the_chip(one_chip, no_compile_cache):
    """K-EXAONE's full-attention block (sparse layer, 8 of 128 experts held) at a bucket of 16
    with 8,192-slot caches, 33.5 MB a session: no array of the joined caches' shape, outputs
    16 x 33.5 MB = 512 MiB, aliased to the donated arguments, and 100.9 MiB of temporaries
    (963.2 MiB while the caches were joined).
    A window block's rings (0.5 MB a session) stay joined."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, rows = 6144, 8192, 16
    sizes = dict(num_heads=64, num_kv_heads=8, head_dim=128, num_experts=128, experts_per_token=8, expert_inner=2048, held=8)
    compiled, leaves = _compiled_batched_step(name_to_block["exaone_moe_block"](hidden, window=0, **sizes), hidden, max_len, rows, one_chip)
    assert leaves == [(1, 8, max_len, 128)] * 2 and _joined(compiled.as_text(), rows, leaves[0]) == 0
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 513 * 2**20 and memory.temp_size_in_bytes < 128 * 2**20
    compiled, leaves = _compiled_batched_step(name_to_block["exaone_moe_block"](hidden, window=128, **sizes), hidden, max_len, rows, one_chip)
    assert leaves == [(1, 8, 128, 128)] * 2 and _joined(compiled.as_text(), rows, leaves[0]) > 0


@pytest.mark.parametrize("mixer, rows, temporaries_gb", [("minicpm4", 32, 1.0), ("lightning-attn", 32, 0.2)])
def test_sala_batched_step_of_32_sessions_fits_the_chip(one_chip, no_compile_cache, mixer, rows, temporaries_gb):
    """The batched program of a MiniCPM-SALA block at the published widths and 32,768 slots,
    a bucket of 32, as `DecodeSessionManager._batched_fn` builds it over the caches' leaves
    (three arrays a session, handed to the sparse block row by row, `decode_rows_apart`; or
    one state, joined): it compiles, names its scopes and gathers where it is the sparse
    block, and its temporaries stay under what 8.88 GB of weights and 2.62 GB of sessions
    leave of the chip (ISSUE 41: 0.43 GB and 0.07 GB when written; 2.76 GB while the sparse
    block's caches were joined, copied and split around the step)."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len = 4096, 32768
    compiled, _leaves = _compiled_batched_step(name_to_block["minicpm_sala_block"](hidden, mixer=mixer), hidden, max_len, rows, one_chip)
    text = compiled.as_text()
    if mixer == "minicpm4":
        assert "sparse_select" in text and "sparse_attend" in text and " gather(" in text
    else:
        assert "lightning_step" in text
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries_gb * 1e9


@pytest.mark.parametrize("mixer", ["minicpm4", "lightning-attn"])
def test_sala_prompt_chunk_of_4096_positions_fits_the_chip(one_chip, no_compile_cache, mixer):
    """A chunk of 4,096 positions continuing a session at 32,768 slots, at the published
    widths: the block-sparse chunk attends in blocks of queries (dense scores of the chunk
    against a 24k cache would be 15 GB), the lightning one scans sub-chunks."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, chunk = 4096, 32768, 4096
    module = name_to_block["minicpm_sala_block"](hidden, mixer=mixer)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    cache = on_chip(jax.eval_shape(lambda: module.init_decode_cache(1, max_len)))
    step = jax.jit(lambda p, x, cache, index, length: module.apply({"params": p}, x, *cache, index, length), donate_argnums=(2,))
    scalar = _shape((), jnp.int32, one_chip)
    compiled = step.lower(on_chip(params), _shape((1, chunk, hidden), jnp.float32, one_chip), cache, scalar, scalar).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9  # 1.23 GB and 0.24 GB when written


@pytest.mark.parametrize("mlp", ["dense", "sparse"])
def test_latent_batched_step_of_32_sessions_fits_the_chip(one_chip, no_compile_cache, mlp):
    """The batched program of a GigaChat3.1 block (`deepseek_v3_block`) at the published widths
    and 12,288 slots, a bucket of 32, as `DecodeSessionManager._batched_fn` builds it over the
    rows' own arrays (`decode_rows_apart`: ONE array of 576 values a position a session): it
    compiles, names its scopes, keeps each row's array in the layout that puts the positions
    on the lanes (576 is no multiple of 128: neither the latent nor the shared key is padded),
    expands no cache, and its temporaries stay small beside 10.61 GB of weights and 2.26 GB of
    sessions (ISSUE 43: 0.03 GB when written; the 32 new arrays are outputs, 0.45 GB, since ISSUE 50
    in the donated arguments' buffers)."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, rows = 7168, 12288, 32
    module = name_to_block["deepseek_v3_block"](hidden, mlp=mlp, v_head_dim=192, rope_theta=100000.0, rope_factor=64.0, held=8)
    compiled, leaves = _compiled_batched_step(module, hidden, max_len, rows, one_chip)
    text = compiled.as_text()
    assert leaves == [(1, max_len, 576)] and "latent_absorb" in text and "latent_attend" in text and "latent_expand" not in text
    assert ("moe_experts" in text) == (mlp == "sparse")
    assert f"bf16[1,{max_len},576]{{1,2,0:" in text  # the positions are the minor axis: 14.16 MB a session, as the gauge counts it
    analysis = compiled.memory_analysis()
    assert analysis.temp_size_in_bytes < 0.2e9 and analysis.output_size_in_bytes < rows * max_len * 576 * 2 + 0.05e9


@pytest.mark.parametrize("mlp", ["dense", "sparse"])
def test_latent_prompt_chunk_of_2048_positions_fits_the_chip(one_chip, no_compile_cache, mlp):
    """A chunk of 2,048 positions continuing a session at 12,288 slots, at the published
    widths, in the expanded form: keys in blocks of 1,024 and queries in blocks of 512 under a
    running softmax (the chunk's scores against 10k cached positions whole would be 5 GB)."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, chunk = 7168, 12288, 2048
    module = name_to_block["deepseek_v3_block"](hidden, mlp=mlp, v_head_dim=192, rope_theta=100000.0, rope_factor=64.0, held=8)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    cache = on_chip(jax.eval_shape(lambda: module.init_decode_cache(1, max_len)))
    step = jax.jit(lambda p, x, cache, index: module.apply({"params": p}, x, *cache, index), donate_argnums=(2,))
    compiled = step.lower(on_chip(params), _shape((1, chunk, hidden), jnp.float32, one_chip), cache, _shape((), jnp.int32, one_chip)).compile()
    assert "latent_expand" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9  # 0.36 GB dense and 1.01 GB sparse when written


NEMOTRON = dict(held=64)  # every other size is the published one, the block's default


@pytest.mark.parametrize("kind,leaves,temporaries_gb", [("mamba", 2, 0.2), ("attention", 2, 0.05), ("experts", 0, 0.05)])
def test_nemotron_batched_step_of_16_sessions_fits_the_chip(one_chip, no_compile_cache, kind, leaves, temporaries_gb):
    """The batched program of each kind of `nemotron_h_block` at the published widths and 12,288 slots, a bucket
    of 16, as `DecodeSessionManager._batched_fn` builds it: a mixer's over the rows' own windows and states
    (`decode_rows_apart`: no array of 16 states joined, every leaf aliased to an output), the attention's over the
    rows' own caches, an expert layer's over NO cache at all (a tree of zero leaves: nothing donated, nothing
    handed back), two grouped matmuls a call."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, rows = 4096, 12288, 16
    module = name_to_block["nemotron_h_block"](hidden, kind=kind, **NEMOTRON)
    compiled, shapes = _compiled_batched_step(module, hidden, max_len, rows, one_chip)
    text = compiled.as_text()
    assert len(shapes) == leaves and compiled.memory_analysis().temp_size_in_bytes < temporaries_gb * 1e9
    if kind == "mamba":
        assert shapes == [(1, 3, 10240), (1, 128, 64, 128)] and "ssm_step" in text and "ssm_conv" in text and "ssm_scan" not in text
        assert f"f32[{rows},128,64,128]" not in text  # the rows' states are never joined
    elif kind == "attention":
        assert shapes == [(1, 2, max_len, 128)] * 2 and _joined(text, rows, shapes[0]) == 0
    else:
        assert "moe_experts" in text and text.count("ragged-dot-none") >= 2 and compiled.memory_analysis().alias_size_in_bytes == 0


@pytest.mark.parametrize("kind,scope,temporaries_gb", [("mamba", "ssm_scan", 0.7), ("attention", None, 0.3), ("experts", "moe_experts", 1.0)])
def test_nemotron_prompt_chunk_of_2048_positions_fits_the_chip(one_chip, no_compile_cache, kind, scope, temporaries_gb):
    """A chunk of 2,048 positions continuing a session at 12,288 slots: the mixer's scan in sub-chunks of 128
    (0.43 GB of temporaries when written), the attention's chunk against its cache a block of 512 keys at a time
    (0.13 GB: the chunk's scores against 10k cached positions whole would be 3 GB), an expert layer's 45,056 pairs
    (0.68 GB)."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, chunk = 4096, 12288, 2048
    module = name_to_block["nemotron_h_block"](hidden, kind=kind, **NEMOTRON)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    cache = on_chip(jax.eval_shape(lambda: module.init_decode_cache(1, max_len)))
    scalar = _shape((), jnp.int32, one_chip)
    step = jax.jit(lambda p, x, cache, *rest: module.apply({"params": p}, x, *cache, *rest), donate_argnums=(2,))
    compiled = step.lower(on_chip(params), _shape((1, chunk, hidden), jnp.float32, one_chip), cache, scalar,
                          *((scalar,) if module.decode_takes_length else ())).compile()
    assert scope is None or scope in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries_gb * 1e9


OURO = dict(num_heads=16, ffn_inner=5632, rope_theta=1e6, rms_eps=1e-6, total_ut_steps=4)  # Ouro-2.6B's published widths at hidden 2,048


def test_looped_batched_step_of_16_sessions_fits_the_chip(one_chip, no_compile_cache):
    """Ouro-2.6B's block at a bucket of 16 with 1,792-slot caches (14.7 MB a pass a session): the program is handed ONE
    pass's pair a row, whatever the passes of the rows (the manager picks them), steps on the rows' own arrays where they
    lie, aliases every one to an output, and splits under the two named scopes a trace reads."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, rows = 2048, 1792, 16
    compiled, leaves = _compiled_batched_step(name_to_block["ouro_block"](hidden, **OURO), hidden, max_len, rows, one_chip)
    text = compiled.as_text()
    assert leaves == [(1, 16, max_len, 128)] * 2 and _joined(text, rows, leaves[0]) == 0
    assert "loop_attention" in text and "loop_mlp" in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < 236 * 2**20 and memory.temp_size_in_bytes < 16 * 2**20  # 16 x 14.7 MB of caches, aliased


def test_looped_prompt_of_1024_positions_fits_the_chip(one_chip, no_compile_cache):
    """One pass's prefill of the cell's longest prompt: the chunk into the pass's pair, plain causal attention within the chunk (`_cache_attention`)."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, chunk = 2048, 1792, 1024
    module = name_to_block["ouro_block"](hidden, **OURO)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    cache = on_chip(jax.eval_shape(lambda: module.init_decode_cache(1, max_len)))
    step = jax.jit(lambda p, x, cache, index: module.apply({"params": p}, x, *cache, index), donate_argnums=(2,))
    compiled = step.lower(on_chip(params), _shape((1, chunk, hidden), jnp.float32, one_chip), cache, _shape((), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("rows", [16, 32])
@pytest.mark.parametrize("kind,shapes,temporaries_gb", [("mamba", [(1, 3, 4352), (1, 64, 64, 128)], 0.05), ("attention", [(1, 8, 12288, 64)] * 2, 0.05)])
def test_granite_batched_step_of_a_cohort_fits_the_chip(one_chip, no_compile_cache, kind, shapes, temporaries_gb, rows):
    """The batched program of each kind of `granite_h_block` at the published widths (the block's defaults) and 12,288
    slots, at the bucket of 16 that the cell's 32 sessions travel in and the 32 that 64 sessions would (the issue's first
    size, measured in PR 59): a mixer's over the rows' own windows and states (no array of the rows' states joined), the attention's over the rows' own caches; every leaf aliased to an output; the
    MLP's and the attention's scopes in the text beside the mixer's. THE CACHE'S LAYOUT, read off the chip's compiler:
    an array `[1, 8, 12288, 64]` bf16 lies with its slots as the minor axis (`{2,3,1,0:T(8,128)(2,1)}`), so the
    64-wide heads are NOT padded to a lane row of 128 and the arguments hold the rows' caches at their logical bytes."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len = 2048, 12288
    compiled, leaves = _compiled_batched_step(name_to_block["granite_h_block"](hidden, kind=kind), hidden, max_len, rows, one_chip)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert leaves == shapes and memory.temp_size_in_bytes < temporaries_gb * 1e9 and "shared_mlp" in text
    weights = 4 * (76_182_976 if kind == "mamba" else 60_821_504)
    if kind == "mamba":
        assert "ssm_step" in text and "ssm_conv" in text and "ssm_scan" not in text and f"f32[{rows},64,64,128]" not in text
        assert memory.argument_size_in_bytes < weights + rows * (2_097_152 + 4 * 4352 * 2) + 2**20  # a window's 3 rows lie in a tile of 4
    else:
        assert "nope_attend" in text and _joined(text, rows, leaves[0]) == 0
        header = next(line for line in text.splitlines() if "entry_computation_layout" in line)
        assert "bf16[1,8,12288,64]{2,3,1,0:T(8,128)(2,1)}" in header and "bf16[1,8,12288,64]{3,2,1,0" not in header
        assert memory.argument_size_in_bytes < weights + rows * 2 * 12288 * 8 * 64 * 2 + 2**20  # 25.2 MB a row of caches (805.3 MB at 32 rows): unpadded


@pytest.mark.parametrize("kind,scope,temporaries_gb", [("mamba", "ssm_scan", 0.2), ("attention", "nope_attend", 0.3)])
def test_granite_prompt_chunk_of_2048_positions_fits_the_chip(one_chip, no_compile_cache, kind, scope, temporaries_gb):
    """A chunk of 2,048 positions continuing a session at 12,288 slots: the mixer's scan in sub-chunks of 256 (0.07 GB of
    temporaries when written), the attention's chunk against its cache a block of 512 keys at a time (0.14 GB)."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, chunk = 2048, 12288, 2048
    module = name_to_block["granite_h_block"](hidden, kind=kind)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    on_chip = lambda tree: jax.tree_util.tree_map(lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    cache = on_chip(jax.eval_shape(lambda: module.init_decode_cache(1, max_len)))
    scalar = _shape((), jnp.int32, one_chip)
    step = jax.jit(lambda p, x, cache, *rest: module.apply({"params": p}, x, *cache, *rest), donate_argnums=(2,))
    compiled = step.lower(on_chip(params), _shape((1, chunk, hidden), jnp.float32, one_chip), cache, scalar,
                          *((scalar,) if module.decode_takes_length else ())).compile()
    assert scope in compiled.as_text() and "shared_mlp" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries_gb * 1e9


@pytest.mark.parametrize(
    "shape,causal,backward",
    [
        ((32, 512, 12, 64), False, True),  # albert-base.swarm2: one whole-row tile a head
        ((4, 512, 32, 128), True, True),  # mistral-7b-span8.finetune
        ((1, 1024, 32, 128), True, False),  # mistral-7b-span8.decode32: the longest prefill
        ((1, 640, 32, 128), True, False),  # five 128s: a whole row of keys beside 128-row query blocks
    ],
)
def test_flash_kernels_compile_under_mosaic_at_the_cells_shapes(one_chip, no_compile_cache, shape, causal, backward):
    from hivemind_tpu.ops import pallas_attention

    operand = _shape(shape, jnp.bfloat16, one_chip)
    compiled = pallas_attention._flash_forward.lower(operand, operand, operand, causal=causal).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # the log-sum-exp leaves in its own width: no [batch*heads, seq, 128] float32 among the outputs
    batch, seq, heads, head_dim = shape
    assert compiled.memory_analysis().output_size_in_bytes < 2 * batch * seq * heads * (head_dim * 2 + 4)
    if backward:
        lse = _shape((batch, heads, seq), jnp.float32, one_chip)
        compiled = pallas_attention._flash_backward.lower(
            operand, operand, operand, operand, lse, operand, causal=causal).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1

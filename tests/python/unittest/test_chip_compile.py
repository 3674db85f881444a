"""Compile the main path's kernels for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a DESCRIBED
v5e:2x2 host (``jax.experimental.topologies``); nothing runs.  This is the
only file that describes the chip: the call happens inside a module-scoped
fixture (never at import, in a ``skipif``, in ``parametrize`` or in
``conftest.py``), because only one process may hold the TPU library and every
xdist worker imports every test file.  Each case compiles in this process and
takes about two seconds; JAX's persistent compilation cache is off around
them (an entry written for a described device cannot be read back).

What interpret mode cannot show and these do: Mosaic accepts the in-kernel
dropout's PRNG seeding, the kernels lower under a mesh without gathering the
batch, the fast-memory envelope (``flash_vmem_bytes``) is on the safe side
of the compiler's own accounting, and a bf16 caller's products reach the
MXU as bf16 (the kernels' Mosaic modules are read before they are handed to
the compiler).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_attention as pa, pallas_rotary as pr


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %r" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _fwd_bwd(causal, dropout_p=0.0, **kw):
    def f(q, k, v, key):
        def loss(q, k, v):
            out = pa.flash_attention(
                q, k, v, causal=causal, interpret=False,
                dropout_p=dropout_p,
                dropout_key=key if dropout_p else None, **kw)
            return out.astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    return f


def _compile(fn, shape, dtype, sharding, key_sharding=None):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=key_sharding or sharding)
    return jax.jit(fn).lower(x, x, x, key).compile().as_text()


@pytest.mark.parametrize("shape,causal,dropout_p", [
    ((16, 512, 12, 64), False, 0.0),     # BERT-base, batch 16
    ((16, 512, 12, 64), False, 0.1),     # ... with in-kernel dropout
    ((4, 2048, 12, 64), True, 0.0),
    ((1, 8192, 12, 64), True, 0.0),      # asks for more than default VMEM
    ((4, 1024, 8, 32), True, 0.1),       # four heads a grid step
    ((2, 1024, 5, 80), True, 0.0),       # no rows layout: head-major view
])
def test_flash_fwd_bwd_compiles_for_v5e(topo, shape, causal, dropout_p):
    text = _compile(_fwd_bwd(causal, dropout_p), shape, jnp.bfloat16,
                    SingleDeviceSharding(topo.devices[0]))
    # forward, dq and dkv: Mosaic kernels, not the interpreter
    assert text.count("tpu_custom_call") == 3


@pytest.fixture
def mosaic_modules(monkeypatch):
    """The text of every Mosaic module lowered while the test runs."""
    from jax._src import tpu_custom_call

    seen = []
    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def grab(module, **kw):
        seen.append(str(module))
        return lower(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", grab)
    return seen


@pytest.mark.parametrize("cell", ["bert_base_t512", "sdar_30b_a3b_bd4k"])
def test_the_cells_kernels_feed_the_mxu_bf16(topo, mosaic_modules, cell):
    """Both cells' shapes (``bf16[64,512,768]`` rows, two heads of 64 a
    grid step; 32 query heads of 128 over 4 KV heads head-major, 8,192
    positions under the block-diffusion mask): every ``tpu.matmul`` of the
    three kernels
    takes bf16 operands and accumulates in float32, and no block of Q, K or
    V is widened: the forward and dkv kernels hold no ``extf`` at all (their
    only casts are p's and ds's ``truncf``), the dq kernel two a head, dO's
    and O's for the float32 products of Δ = rowsum(dO ∘ O)."""
    one = SingleDeviceSharding(topo.devices[0])
    if cell == "bert_base_t512":
        q = kv = jax.ShapeDtypeStruct((64, 512, 12, 64), jnp.bfloat16,
                                      sharding=one)
        # the body is traced once a head of the grid step's two
        kw, products, heads = {}, (2 * 2, 3 * 2, 4 * 2), 2
    else:
        q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                                 sharding=one)
        kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16,
                                  sharding=one)
        # the body is traced once a class of tiles: 3, 3 and 4 ranges
        kw, products, heads = {"mask": pa.block_diffusion_mask(4096, 4)}, \
            (2 * 3, 3 * 3, 4 * 4), 1

    def f(q, k, v):
        def loss(q, k, v):
            return pa.flash_attention(q, k, v, interpret=False, **kw) \
                .astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(f).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    if cell == "bert_base_t512":    # the rows as they lie
        assert "bf16[64,512,768]" in text and " transpose(" not in text
    assert len(mosaic_modules) == 3             # forward, dq, dkv
    for module, n, widened in zip(mosaic_modules, products,
                                  (0, 2 * heads, 0)):
        matmuls = re.findall(
            r"tpu\.matmul.*?: (vector<[^>]*>), (vector<[^>]*>), "
            r"(vector<[^>]*>)", module)
        assert len(matmuls) == n, (len(matmuls), n)
        for lhs, rhs, acc in matmuls:
            assert lhs.endswith("xbf16>") and rhs.endswith("xbf16>") \
                and acc.endswith("xf32>"), (lhs, rhs, acc)
        assert module.count("arith.extf") == widened
    if cell == "sdar_30b_a3b_bd4k":     # one head a step: no lane is masked
        return
    # no mask, no padding: the only index is the lane's, the only selects
    # the heads' lanes (a left operand's, a result's), none on a tile
    for module in mosaic_modules:
        assert "512x512xi1" not in module


def test_block_diffusion_kernels_compile_for_v5e_with_grouped_kv(topo):
    """SDAR's shape: 8,192 positions [xt ; x0], 32 query heads of 128 over
    4 KV heads, the rule evaluated in the kernels (Mosaic takes no select
    between booleans: ``mask_allowed`` is logic on comparisons only)."""
    mask = pa.block_diffusion_mask(4096, 4)
    one = SingleDeviceSharding(topo.devices[0])

    def f(q, k, v):
        def loss(q, k, v):
            return pa.flash_attention(q, k, v, mask=mask, interpret=False) \
                .astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16, sharding=one)
    text = jax.jit(f).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "8192,8192" not in text                  # no score matrix
    # the head-major view: every head a batch row
    assert "bf16[32,8192,128]" in text and "bf16[4,8192,128]" in text


@pytest.mark.parametrize("rule", ["window", "causal"])
def test_laguna_calls_compile_for_v5e_with_grouped_kv(topo, mosaic_modules,
                                                      rule):
    """laguna_xs2_t8k's two calls: 8,192 positions, 8 KV heads of 128 under
    64 query heads and a window of 512 keys, or under 48 and the causal
    rule.  The window of one tile traces the cut body alone (2, 3 and 4
    products); the causal call traces it twice (whole and cut tiles).  bf16
    into every product, no score matrix in the program."""
    heads, kw, bodies = (64, {"mask": pa.window_mask(512)}, 1) \
        if rule == "window" else (48, {"causal": True}, 2)
    one = SingleDeviceSharding(topo.devices[0])

    def f(q, k, v):
        def loss(q, k, v):
            return pa.flash_attention(q, k, v, interpret=False, **kw) \
                .astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one)
    text = jax.jit(f).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "%d,8192,8192" % heads not in text       # no score matrix
    assert "bf16[%d,8192,128]" % heads in text and "bf16[8,8192,128]" in text
    assert len(mosaic_modules) == 3             # forward, dq, dkv
    for module, n in zip(mosaic_modules, (2, 3, 4)):
        matmuls = re.findall(
            r"tpu\.matmul.*?: (vector<[^>]*>), (vector<[^>]*>), "
            r"(vector<[^>]*>)", module)
        assert len(matmuls) == n * bodies, (len(matmuls), n, bodies)
        for lhs, rhs, acc in matmuls:
            assert lhs.endswith("xbf16>") and rhs.endswith("xbf16>") \
                and acc.endswith("xf32>"), (lhs, rhs, acc)
        assert module.count("arith.extf") == (2 if n == 3 else 0)  # Δ's


def test_glm_call_compiles_for_v5e_at_heads_of_256(topo, mosaic_modules):
    """glm47_flash_t8k's call (latent attention in the expanded form):
    8,192 positions, 20 heads of 256 over as many KV heads, causal, bf16.
    Two 128-lane tiles a head and a head's resident K and V twice
    Laguna's: the kernels ask for more than Mosaic's default VMEM and stay
    inside the budget.  Head-major view, the body traced for whole and cut
    tiles, bf16 into every product, no score matrix in the program."""
    need = pa.flash_vmem_bytes(8192, 8192, 256, 2)
    assert pa.VMEM_DEFAULT_BYTES < need <= pa.VMEM_BUDGET_BYTES
    assert pa.use_flash(8192, 8192, 256, False, 2)
    assert pa.heads_per_step(256, 20, 20) == 0
    assert pa.tile_counts(8192, 8192, 512, 512, True) == (136, 120, 16)
    one = SingleDeviceSharding(topo.devices[0])

    def f(q, k, v):
        def loss(q, k, v):
            return pa.flash_attention(q, k, v, causal=True,
                                      interpret=False) \
                .astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16, sharding=one)
    text = jax.jit(f).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "20,8192,8192" not in text               # no score matrix
    assert "bf16[20,8192,256]" in text
    assert len(mosaic_modules) == 3             # forward, dq, dkv
    for module, n in zip(mosaic_modules, (2, 3, 4)):
        matmuls = re.findall(
            r"tpu\.matmul.*?: (vector<[^>]*>), (vector<[^>]*>), "
            r"(vector<[^>]*>)", module)
        assert len(matmuls) == n * 2, (len(matmuls), n)
        for lhs, rhs, acc in matmuls:
            assert lhs.endswith("xbf16>") and rhs.endswith("xbf16>") \
                and acc.endswith("xf32>"), (lhs, rhs, acc)


@pytest.mark.parametrize("dropout_p", [0.0, 0.1])
def test_flash_lowers_on_a_dp_mesh_without_gathering_the_batch(topo,
                                                               dropout_p):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    with pa.mesh_rows(mesh, ("dp",)):
        text = _compile(_fwd_bwd(False, dropout_p), (16, 512, 12, 64),
                        jnp.bfloat16, NamedSharding(mesh, P("dp")),
                        NamedSharding(mesh, P()))
    assert text.count("tpu_custom_call") == 3
    assert "all-gather" not in text
    # every kernel works on a quarter of the batch: 4 rows of (T, H * D)
    assert "bf16[4,512,768]" in text and "bf16[16,512,768]" not in text


def test_flash_without_mesh_rows_cannot_lower_sharded(topo):
    """Why the engines declare ``mesh_rows``: Mosaic kernels have no
    partitioning rule of their own."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    with pytest.raises(Exception, match="shard_map"):
        _compile(_fwd_bwd(False), (16, 512, 12, 64), jnp.bfloat16,
                 NamedSharding(mesh, P("dp")), NamedSharding(mesh, P()))


@pytest.mark.parametrize("shape,dtype,block,dropout_p", [
    ((1, 24576, 2, 128), jnp.float32, 1024, 0.0),  # 83 MiB by the estimate
    ((1, 65536, 2, 64), jnp.bfloat16, 512, 0.0),   # 89 MiB, two heads a step
    ((1, 6144, 2, 256), jnp.float32, 256, 0.0),    # just over the default
    # bf16 copies of p and ds beside the float32 tile, and the keep mask
    ((1, 65536, 2, 64), jnp.bfloat16, 512, 0.1),
    ((1, 16384, 2, 128), jnp.bfloat16, 1024, 0.1),
])
def test_envelope_estimate_covers_the_compilers_need(topo, shape, dtype,
                                                     block, dropout_p):
    """Inside the envelope the kernels ask for ``flash_vmem_bytes`` of
    VMEM, and that is enough for the compiler at the far end of it."""
    need = pa.flash_vmem_bytes(shape[1], shape[1], shape[3],
                               jnp.dtype(dtype).itemsize, block, block)
    assert pa.VMEM_DEFAULT_BYTES < need <= pa.VMEM_BUDGET_BYTES
    text = _compile(_fwd_bwd(True, dropout_p, block_q=block, block_k=block),
                    shape, dtype, SingleDeviceSharding(topo.devices[0]))
    assert text.count("tpu_custom_call") == 3


def test_outside_the_envelope_is_the_repos_own_error(topo):
    shape = (1, 65536, 2, 128)
    assert pa.flash_vmem_bytes(65536, 65536, 128, 4) > pa.VMEM_BUDGET_BYTES
    with pytest.raises(MXNetError, match="VMEM"):
        _compile(_fwd_bwd(True), shape, jnp.float32,
                 SingleDeviceSharding(topo.devices[0]))
    # impl="auto" never sends such a shape to the kernels
    assert not pa.use_flash(65536, 65536, 128, False, 4)
    assert pa.use_flash(8192, 8192, 128, False, 4)


@pytest.mark.parametrize("cell", ["bert_base_t512", "laguna_xs2_t8k"])
def test_no_copy_between_a_projection_and_the_kernels(topo, cell):
    """Projections, ``multi_head_attention``, output projection, forward and
    gradient, as one program.  Heads of 64 reach the kernels as the rows
    the products wrote: nothing as large as Q is copied or transposed on
    the way.  Heads of 128 reach them head-major, which XLA makes the
    LAYOUT of the three products (``{3,1,2,0}`` of ``(B, T, H, D)``): of
    the eight transposes in the jaxpr two are left as copies, the
    attention output's on its way into the output projection and its
    cotangent's on the way back (as before the rows layout; PERF.md
    section 6, PR 33)."""
    from mxnet_tpu.ops.nn import multi_head_attention

    B, T, H, Hkv, D, kw = (64, 512, 12, 12, 64, {}) \
        if cell == "bert_base_t512" \
        else (1, 8192, 64, 8, 128, {"mask": pa.window_mask(512)})
    units = 768 if cell == "bert_base_t512" else 2048
    one = SingleDeviceSharding(topo.devices[0])

    def f(x, wq, wk, wv, wo):
        def loss(x, wq, wk, wv, wo):
            out = multi_head_attention.fn(
                x @ wq, x @ wk, x @ wv, num_heads=H, num_kv_heads=Hkv,
                impl="pallas", **kw)
            return ((out @ wo).astype(jnp.float32) ** 2).sum()

        return jax.value_and_grad(loss, (0, 1, 2, 3, 4))(x, wq, wk, wv, wo)

    arg = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16, sharding=one)
    interpret = pa._default_interpret
    pa._default_interpret = lambda: False       # compile, on this CPU host
    try:
        text = jax.jit(f).lower(
            arg(B, T, units), arg(units, H * D), arg(units, Hkv * D),
            arg(units, Hkv * D), arg(H * D, units)).compile().as_text()
    finally:
        pa._default_interpret = interpret
    assert text.count("tpu_custom_call") == 3
    moved = re.findall(
        r"= bf16\[(?:%d,%d,%d|%d,%d,%d,%d)\]\S* (?:copy|transpose)\("
        % (B, T, H * D, B, T, H, D), text)
    assert len(moved) <= (0 if D == 64 else 2), moved
    if D == 128:
        assert "[1,8192,64,128]{3,1,2,0" in text    # a product, head-major


@pytest.mark.parametrize("shape,heads,norm,rotary", [
    ((1, 8192), 64, False, {"theta": 1e4}),     # Laguna's window layers
    ((1, 8192), 48, False, {                    # its full layers: YaRN
        "rotary_dim": 64, "factor": 1.2,        # tables over 64 of 128
        "inv_freq": tuple(1e4 ** (-i / 32.0) for i in range(32))}),
    ((2, 8192), 32, True, {"theta": 1e6}),      # SDAR's q, with QK-norm
    ((2, 8192), 4, True, {"theta": 1e6}),       # SDAR's k
], ids=["laguna_window", "laguna_full", "sdar_q", "sdar_k"])
def test_placed_pass_compiles_for_v5e(topo, shape, heads, norm, rotary):
    """``ops/pallas_rotary.py`` at the cells' shapes, forward and backward:
    Mosaic takes the lane rotates, the 128-lane slices of a block of rows
    and the head-major out block; the blocks fit the VMEM the call asks
    for; nothing as large as the rows is copied or transposed by XLA on
    either side of the two calls."""
    one = SingleDeviceSharding(topo.devices[0])
    B, T = shape

    def f(x, gain, positions):
        def loss(x, gain):
            return pr.placed(x, pr.rotary_tables(positions, 128, **rotary),
                             heads, rotary.get("rotary_dim"),
                             gain if norm else None, interpret=False) \
                .astype(jnp.float32).sum()

        return jax.value_and_grad(loss, (0, 1) if norm else 0)(x, gain)

    x = jax.ShapeDtypeStruct((B, T, heads * 128), jnp.bfloat16, sharding=one)
    gain = jax.ShapeDtypeStruct((128,), jnp.bfloat16, sharding=one)
    positions = jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one)
    text = jax.jit(f).lower(x, gain, positions).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "placed_fwd" in text and "placed_bwd" in text
    assert "bf16[%d,8192,128]" % (B * heads) in text    # the kernels' view
    assert not re.findall(
        r"= bf16\[(?:%d,%d,%d|%d,%d,128)\]\S* (?:copy|transpose)\("
        % (B, T, heads * 128, B * heads, T), text)


@pytest.mark.parametrize("cell", ["laguna_xs2_t8k", "sdar_30b_a3b_bd4k"])
def test_placed_attention_layer_compiles_for_v5e(topo, cell):
    """Projections, ``placed_attention``, output projection, forward and
    gradient, as one program at a window layer of Laguna and at SDAR's
    layer: seven Mosaic calls (the pass on q and on k, the three flash
    kernels, the pass's backward twice), and of everything as large as q
    XLA copies the attention output into the output projection (and at
    SDAR's shape its cotangent back), nothing on the way INTO the
    kernels."""
    B, T, H, Hkv, norm, kw = (1, 8192, 64, 8, False, {
        "mask": pa.window_mask(512), "theta": 1e4}) \
        if cell == "laguna_xs2_t8k" else (2, 8192, 32, 4, True, {
            "mask": pa.block_diffusion_mask(4096, 4), "theta": 1e6})
    one = SingleDeviceSharding(topo.devices[0])

    def f(x, wq, wk, wv, wo, gq, gk):
        def loss(x, wq, wk, wv, wo, gq, gk):
            positions = jnp.arange(T, dtype=jnp.int32) % 4096
            out = pr.placed_attention(
                x @ wq, x @ wk, x @ wv, positions,
                *((gq, gk) if norm else ()), num_heads=H, num_kv_heads=Hkv,
                **kw)
            return ((out @ wo).astype(jnp.float32) ** 2).sum()

        return jax.value_and_grad(loss, tuple(range(7)))(
            x, wq, wk, wv, wo, gq, gk)

    arg = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16, sharding=one)
    interpret = pa._default_interpret
    pa._default_interpret = lambda: False       # compile, on this CPU host
    try:
        text = jax.jit(f).lower(
            arg(B, T, 2048), arg(2048, H * 128), arg(2048, Hkv * 128),
            arg(2048, Hkv * 128), arg(H * 128, 2048), arg(128),
            arg(128)).compile().as_text()
    finally:
        pa._default_interpret = interpret
    assert text.count("tpu_custom_call") == 7
    moved = re.findall(
        r"= bf16\[(?:%d,%d,%d|%d,%d,%d,128|%d,%d,128)\]\S* "
        r"(?:copy|transpose)\(.*?op_name=\"([^\"]*)\""
        % (B, T, H * 128, B, T, H, B * H, T), text)
    assert len(moved) <= 2, moved
    assert not any("placed" in name for name in moved), moved

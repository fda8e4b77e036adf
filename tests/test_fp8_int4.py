"""fp8 codeword + nibble-packed int4 assignment operand tiers (DESIGN.md
section 15): the float8_e4m3fn codeword quantizer and its round-trip error
bound, nibble pack/unpack/gather/scatter and the ``PackedAssignment``
pytree, uint4 emission from the VQ-update kernel (+ the per-dtype k-limit
guards), fp8/packed kernel parity against the dequantized oracles, the
5-tier precision ladder in kernels/ops.py, dtype-keyed autotuner entries
(no int8-vs-fp8 or uint8-vs-uint4 collisions), the shared ``dtype_nbits``
byte accounting, pack-aware state constructors, the fp8 bitcast payload of
``gather_from_shards``, and end-to-end init/train/infer smoke under the
fp8 and int8+a4 tiers.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from numpy.testing import assert_allclose

from repro.core.codebook import CodebookConfig
from repro.core.conv import (assignment_packed, init_layer_vq_state,
                             refresh_assignment)
from repro.distributed.quantization import (PackedAssignment, dtype_nbits,
                                            gather_nibbles, pack_nibbles,
                                            quantize_codewords,
                                            scatter_nibbles, tree_bytes,
                                            unpack_nibbles)
from repro.kernels import autotune, ops, ref
from repro.kernels.context_ell import context_ell_pallas
from repro.kernels.context_ell import vmem_bytes as context_vmem_bytes
from repro.kernels.spmm_ell import spmm_ell_pallas
from repro.kernels.vq_update import vq_assign_update_pallas

FP8 = jnp.float8_e4m3fn


def _case(b, deg, n, nb, k, f_blk, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ids = jax.random.randint(k1, (b, deg), 0, n).astype(jnp.int32)
    val = jax.random.normal(k2, (b, deg), jnp.float32)
    assign = jax.random.randint(k3, (nb, n), 0, k).astype(jnp.uint8)
    cw = jax.random.normal(k4, (nb, k, f_blk), jnp.float32)
    return ids, val, assign, cw


# ---------------------------------------------------------------------------
# fp8 codeword quantizer
# ---------------------------------------------------------------------------

def test_fp8_quantize_roundtrip_error_bound():
    cw = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 8)) * 3.0
    qt = quantize_codewords(cw, dtype=FP8)
    assert qt.q.dtype == FP8
    assert qt.scale.shape == (4, 1, 8)
    deq = qt.q.astype(jnp.float32) * qt.scale
    # e4m3 keeps >= 3 mantissa bits over the normal range (relative error
    # <= 2^-4) and the subnormal lattice pitch is scale * 2^-9; together:
    bound = np.abs(np.asarray(cw)) / 16.0 \
        + np.asarray(qt.scale) * 2.0 ** -10 * 1.01
    err = np.abs(np.asarray(deq) - np.asarray(cw))
    assert (err <= bound).all()


def test_fp8_quantize_prev_pins_dtype():
    cw = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 4))
    prev = quantize_codewords(cw, dtype=FP8)
    # data-driven requantize (the jitted EMA-update path): dtype comes from
    # the previous snapshot, not from the dtype arg
    nxt = quantize_codewords(cw * 1.01, prev=prev)
    assert nxt.q.dtype == FP8
    nxt8 = quantize_codewords(cw * 1.01, prev=quantize_codewords(cw))
    assert nxt8.q.dtype == jnp.int8


def test_quantize_codewords_rejects_unknown_dtype():
    cw = jnp.zeros((1, 4, 4))
    with pytest.raises((ValueError, KeyError)):
        quantize_codewords(cw, dtype=jnp.float16)


# ---------------------------------------------------------------------------
# nibble packing: pack/unpack identity, gather, scatter
# ---------------------------------------------------------------------------

def test_pack_unpack_identity_all_ids_and_odd_tail():
    # every id 0..15, even and odd lengths (the odd tail pads a 0 nibble)
    for n in (16, 17, 1, 2, 31):
        ids = jnp.arange(n, dtype=jnp.uint8) % 16
        packed = pack_nibbles(ids[None])
        assert packed.dtype == jnp.uint8
        assert packed.shape == (1, (n + 1) // 2)
        out = unpack_nibbles(packed, n)
        assert np.array_equal(np.asarray(out[0]), np.asarray(ids))


def test_gather_scatter_nibbles_match_dense():
    rng = np.random.default_rng(0)
    dense = jnp.asarray(rng.integers(0, 16, (3, 33)), dtype=jnp.uint8)
    packed = pack_nibbles(dense)
    ids = jnp.asarray([0, 32, 7, 8, 31])          # distinct, mixed parity
    got = gather_nibbles(packed, ids)
    assert np.array_equal(np.asarray(got), np.asarray(dense[:, ids]))
    vals = jnp.asarray(rng.integers(0, 16, (3, 5)), dtype=jnp.uint8)
    upd = scatter_nibbles(packed, ids, vals)
    want = dense.at[:, ids].set(vals)
    assert np.array_equal(np.asarray(unpack_nibbles(upd, 33)),
                          np.asarray(want))


def test_packed_assignment_pytree_roundtrip():
    dense = jnp.asarray([[1, 15, 0, 7, 9]], dtype=jnp.uint8)
    pa = PackedAssignment.pack(dense)
    assert pa.shape == (1, 5)
    assert np.array_equal(np.asarray(pa.unpack()), np.asarray(dense))
    # registered pytree: survives jit boundaries with static n
    out = jax.jit(lambda p: p.unpack())(pa)
    assert np.array_equal(np.asarray(out), np.asarray(dense))
    # exact sub-byte accounting: ceil(5/2) bytes per branch
    assert tree_bytes((pa,)) == 3


def test_dtype_nbits_sub_byte_and_hlo_names():
    assert dtype_nbits(jnp.uint4) == 4
    assert dtype_nbits(jnp.int4) == 4
    assert dtype_nbits(jnp.uint8) == 8
    assert dtype_nbits(FP8) == 8
    assert dtype_nbits(jnp.float32) == 32
    assert dtype_nbits("float8_e4m3fn") == 8     # dtype names
    assert dtype_nbits("uint4") == 4
    assert dtype_nbits("bool") == 8


# ---------------------------------------------------------------------------
# uint4 emission from the VQ-update kernel + the per-dtype k-limit guards
# ---------------------------------------------------------------------------

def test_vq_update_emit_uint4_matches_int32():
    x = jax.random.normal(jax.random.PRNGKey(2), (100, 8))
    cw = jax.random.normal(jax.random.PRNGKey(3), (16, 8))
    i32, q32, c32, s32 = vq_assign_update_pallas(x, cw, interpret=True)
    i4, q4, c4, s4 = vq_assign_update_pallas(x, cw, interpret=True,
                                             emit_dtype=jnp.uint4)
    assert i4.dtype == jnp.uint4
    assert np.array_equal(np.asarray(i32), np.asarray(i4).astype(np.int32))
    assert_allclose(np.asarray(q32), np.asarray(q4))
    assert np.array_equal(np.asarray(c32), np.asarray(c4))


def test_vq_update_emit_uint4_needs_k16():
    x = jnp.zeros((8, 4))
    cw = jnp.zeros((32, 4))
    with pytest.raises(ValueError, match="uint4.*k <= 16"):
        vq_assign_update_pallas(x, cw, interpret=True, emit_dtype=jnp.uint4)


def test_vq_update_emit_uint8_needs_k256():
    x = jnp.zeros((8, 4))
    cw = jnp.zeros((300, 4))
    with pytest.raises(ValueError, match="uint8.*k <= 256"):
        vq_assign_update_pallas(x, cw, interpret=True, emit_dtype=jnp.uint8)


def test_vq_update_emit_rejects_unsupported_dtype_naming_it():
    x = jnp.zeros((8, 4))
    cw = jnp.zeros((8, 4))
    with pytest.raises(ValueError, match="int16"):
        vq_assign_update_pallas(x, cw, interpret=True, emit_dtype=jnp.int16)
    # int32 is the documented always-valid fallback
    i, _, _, _ = vq_assign_update_pallas(x, cw, interpret=True,
                                         emit_dtype=jnp.int32)
    assert i.dtype == jnp.int32


# ---------------------------------------------------------------------------
# kernel parity: fp8 codewords, packed assignment tables (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,deg,n,nb,k,f_blk", [
    (128, 8, 999, 4, 16, 8),       # odd n: padded tail
    (40, 13, 300, 8, 16, 5),       # 8 x 5, D % 8 != 0
    (20, 3, 50, 1, 1, 4),          # a one-word codebook
])
@pytest.mark.parametrize("with_wt", [False, True])
def test_context_ell_fp8_packed_parity(b, deg, n, nb, k, f_blk, with_wt):
    ids, val, assign, cw = _case(b, deg, n, nb, k, f_blk)
    qt = quantize_codewords(cw, dtype=FP8)
    deq = qt.q.astype(jnp.float32) * qt.scale
    pa = PackedAssignment.pack(assign)
    w_t = jax.random.normal(jax.random.PRNGKey(9), (nb * f_blk, 24)) \
        if with_wt else None
    got = context_ell_pallas(ids, val, pa, qt.q, cw_scale=qt.scale,
                             w_t=w_t, interpret=True)
    want = ref.context_ell(ids, val, assign, deq, w_t)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_context_ell_packed_int8_parity():
    ids, val, assign, cw = _case(64, 4, 200, 2, 16, 8, seed=1)
    qt = quantize_codewords(cw)
    deq = qt.q.astype(jnp.float32) * qt.scale
    pa = PackedAssignment.pack(assign)
    got = context_ell_pallas(ids, val, pa, qt.q, cw_scale=qt.scale,
                             interpret=True)
    want = ref.context_ell(ids, val, assign, deq)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ref_context_ell_unpacks_packed():
    ids, val, assign, cw = _case(32, 4, 100, 2, 16, 8, seed=2)
    pa = PackedAssignment.pack(assign)
    a = ref.context_ell(ids, val, pa, cw)
    b = ref.context_ell(ids, val, assign, cw)
    assert_allclose(np.asarray(a), np.asarray(b))


def test_spmm_ell_fp8_parity():
    from repro.distributed.quantization import quantize_tensor
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    ids = jax.random.randint(k1, (64, 8), 0, 100).astype(jnp.int32)
    val = jax.random.normal(k2, (64, 8))
    x = jax.random.normal(k3, (100, 16))
    qt = quantize_tensor(x, dtype=FP8)
    assert qt.q.dtype == FP8
    deq = qt.q.astype(jnp.float32) * qt.scale
    got = spmm_ell_pallas(ids, val, qt.q, x_scale=qt.scale, interpret=True)
    want = ref.spmm_ell(ids, val, deq)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# precision ladder + dispatch
# ---------------------------------------------------------------------------

def test_precision_ladder_helpers():
    assert ops.PRECISIONS == ("fp32", "int8", "fp8", "int8+a4", "fp8+a4")
    assert ops.precision_codeword_dtype("fp32") is None
    assert ops.precision_codeword_dtype("int8") == jnp.dtype(jnp.int8)
    assert ops.precision_codeword_dtype("fp8") == jnp.dtype(FP8)
    assert ops.precision_codeword_dtype("fp8+a4") == jnp.dtype(FP8)
    assert not ops.precision_packs_assignment("fp8")
    assert ops.precision_packs_assignment("int8+a4")
    assert ops.precision_packs_assignment("fp8+a4")


def test_configure_rejects_unknown_precision_listing_tiers():
    with pytest.raises(ValueError) as ei:
        ops.configure_kernel_precision("int4")
    msg = str(ei.value)
    for tier in ops.PRECISIONS:
        assert tier in msg
    assert ops.kernel_precision() in ops.PRECISIONS   # state unchanged


def test_kernel_precision_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_PRECISION", "fp8+a4")
    assert ops.kernel_precision() == "fp8+a4"
    monkeypatch.setenv("REPRO_KERNEL_PRECISION", "nope")
    with pytest.raises(ValueError, match="fp8\\+a4"):
        ops.kernel_precision()


def _traced_context_variants(assignment, k, f_blk, deg, b=256):
    """The variants ``ops`` dispatches one context term to, read from the
    trace-time counter while tracing the kernel path (through a fresh
    function each time: a trace of the same function is cached)."""
    from repro.analysis.trace_count import CONTEXT_TRACE_COUNT
    sds = jax.ShapeDtypeStruct
    nb = assignment.shape[0]
    before = CONTEXT_TRACE_COUNT.snapshot()
    jax.make_jaxpr(lambda *a: ops._context_ell_kernel(*a))(
        sds((b, deg), jnp.int32), sds((b, deg), jnp.float32), assignment,
        sds((nb, k, f_blk), jnp.float32), None, None)
    delta = CONTEXT_TRACE_COUNT.delta(before)
    return sorted(key.split(".")[1] for key, n in delta.items() if n)


def test_context_dispatch_packed_halves_table_budget():
    # the packed table halves the uint8 table's HBM bytes, and no longer
    # any VMEM budget: the dispatch charges what the fused kernel holds
    # (codebook tables, id and value blocks), not the assignment table
    # XLA gathers ahead of it, so int32, uint8 and nibble-packed tables
    # cross at the same budget, at any node count
    nb, k, f_blk, deg = 4, 16, 8, 16
    held_mb = context_vmem_bytes(nb, k, f_blk, deg) / 2 ** 20
    sds = jax.ShapeDtypeStruct
    try:
        for n in (1_000, 10_000_000):
            tables = (sds((nb, n), jnp.int32), sds((nb, n), jnp.uint8),
                      PackedAssignment(sds((nb, n // 2), jnp.uint8), n))
            assert tables[2].packed.size * 2 == tables[1].size
            for budget_mb, want in ((held_mb * 1.01, "fused"),
                                    (held_mb * 0.99, "loop")):
                ops.configure_context_dispatch(reset=True,
                                               vmem_budget_mb=budget_mb)
                for table in tables:
                    assert _traced_context_variants(
                        table, k, f_blk, deg) == [want], (n, table, want)
    finally:
        ops.configure_context_dispatch(reset=True)


def test_autotune_keys_no_tier_collisions(tmp_path, monkeypatch):
    # int8 vs fp8 spmm sources and int8 vs fp8 context codebooks share an
    # itemsize but are distinct operand regimes: their cache entries must
    # never collide (REPRO_AUTOTUNE=1 + fp8 vs int8)
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.clear()
    try:
        keys = {autotune.cache_key("spmm", (1000, 16, 1), jnp.int8),
                autotune.cache_key("spmm", (1000, 16, 1), FP8),
                autotune.cache_key("context", (2, 16, 8, 8), jnp.int8),
                autotune.cache_key("context", (2, 16, 8, 8), FP8)}
        assert len(keys) == 4
        cfg8 = autotune.tuned_context(2, 16, 8, 8, dtype=jnp.int8)
        cfg4 = autotune.tuned_context(2, 16, 8, 8, dtype=FP8)
        assert cfg8 is not None and cfg4 is not None
        k8 = autotune.cache_key("context", (2, 16, 8, 8), jnp.int8)
        k4 = autotune.cache_key("context", (2, 16, 8, 8), FP8)
        assert autotune.lookup(k8) == cfg8
        assert autotune.lookup(k4) == cfg4
    finally:
        autotune.clear()


# ---------------------------------------------------------------------------
# pack-aware state constructors
# ---------------------------------------------------------------------------

def test_init_layer_vq_state_fp8_a4(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_PRECISION", "fp8+a4")
    cfg = CodebookConfig(k=16, f_prod=8)
    assert assignment_packed(cfg)
    st = init_layer_vq_state(jax.random.PRNGKey(0), 101, 16, 16, cfg)
    assert isinstance(st.assignment, PackedAssignment)
    assert st.assignment.shape[1] == 101
    assert st.qcw is not None and st.qcw.feat.q.dtype == FP8
    # k > 16 falls back to the uint8 table under the same tier
    cfg_big = CodebookConfig(k=32, f_prod=8)
    assert not assignment_packed(cfg_big)
    st_big = init_layer_vq_state(jax.random.PRNGKey(0), 50, 16, 16, cfg_big)
    assert st_big.assignment.dtype == jnp.uint8


def test_refresh_assignment_packed_matches_dense(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_PRECISION", "int8+a4")
    cfg = CodebookConfig(k=16, f_prod=8)
    st = init_layer_vq_state(jax.random.PRNGKey(0), 64, 16, 16, cfg)
    nb = st.assignment.shape[0]
    batch_ids = jnp.asarray([3, 7, 0, 20, 63, 11])       # distinct ids
    new = jnp.tile(jnp.asarray([[1, 2, 3, 4, 5, 15]], dtype=jnp.uint8),
                   (nb, 1))
    st2 = refresh_assignment(st, batch_ids, new)
    dense = st.assignment.unpack().at[:, batch_ids].set(new)
    assert np.array_equal(np.asarray(st2.assignment.unpack()),
                          np.asarray(dense))


def test_quantize_vq_states_tiers_and_guards():
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import (GNNConfig, init_vq_states,
                                  quantize_vq_states)
    g = synthetic_arxiv(n=100, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=1,
                    codebook=CodebookConfig(k=16, f_prod=4))
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    vq_f8a4 = quantize_vq_states(vq, cfg, precision="fp8+a4")
    assert isinstance(vq_f8a4[0].assignment, PackedAssignment)
    assert vq_f8a4[0].qcw.feat.q.dtype == FP8
    # tier switch rebuilds the snapshot in the new dtype and unpacks
    vq_i8 = quantize_vq_states(vq_f8a4, cfg, precision="int8")
    assert vq_i8[0].assignment.dtype == jnp.uint8
    assert vq_i8[0].qcw.feat.q.dtype == jnp.int8
    # +a4 guard names the usable fallback tier
    cfg_big = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                        n_out=g.num_classes, n_layers=1,
                        codebook=CodebookConfig(k=32, f_prod=4))
    vq_big = init_vq_states(jax.random.PRNGKey(1), cfg_big, g.n)
    with pytest.raises(ValueError, match="k <= 16"):
        quantize_vq_states(vq_big, cfg_big, precision="fp8+a4")


# ---------------------------------------------------------------------------
# fp8 shard gather payload
# ---------------------------------------------------------------------------

def test_gather_from_shards_fp8_bit_exact():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import gather_from_shards

    ndev = jax.local_device_count()
    mesh = jax.make_mesh((ndev,), ("shard",))
    n_local, f = 8, 5
    table = jax.random.normal(
        jax.random.PRNGKey(0), (ndev * n_local, f)).astype(FP8)
    ids = jax.random.randint(jax.random.PRNGKey(1), (ndev, 6), 0,
                             ndev * n_local)
    run = shard_map(
        lambda tab, i: gather_from_shards(tab, i.reshape(-1), "shard"),
        mesh=mesh, in_specs=(P("shard"), P("shard")), out_specs=P("shard"))
    out = run(table, ids)
    assert out.dtype == FP8
    want = np.asarray(table)[np.asarray(ids).reshape(-1)]
    assert np.array_equal(np.asarray(out).view(np.uint8),
                          want.view(np.uint8))


# ---------------------------------------------------------------------------
# end-to-end smoke under the new tiers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier", ["fp8", "int8+a4"])
def test_tier_inference_agreement(tier, monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_PRECISION", raising=False)
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import (GNNConfig, init_gnn, init_vq_states,
                                  quantize_vq_states)
    from repro.train.gnn_trainer import vq_inference
    g = synthetic_arxiv(n=300, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=16, f_prod=4))
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    y32 = vq_inference(params, vq, g, cfg, batch_size=100)
    yq = vq_inference(params, quantize_vq_states(vq, cfg, precision=tier),
                      g, cfg, batch_size=100)
    agree = float((np.argmax(np.asarray(y32), -1) ==
                   np.argmax(np.asarray(yq), -1)).mean())
    assert agree >= 0.95


@pytest.mark.parametrize("tier", ["fp8", "fp8+a4"])
def test_tier_training_smoke(tier):
    from repro.graph.datasets import synthetic_arxiv
    from repro.models.gnn import GNNConfig
    from repro.train.gnn_trainer import train_vq
    g = synthetic_arxiv(n=300, seed=0)
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=16,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=16, f_prod=4))
    ops.configure_kernel_precision(tier)
    try:
        r = train_vq(g, cfg, epochs=2, batch_size=100, eval_every=100)
    finally:
        ops.configure_kernel_precision(reset=True)
    st = r["vq_states"][0]
    if tier.endswith("+a4"):
        assert isinstance(st.assignment, PackedAssignment)
    assert st.qcw is not None and st.qcw.feat.q.dtype == FP8
    assert np.isfinite(r["final"]["val"])

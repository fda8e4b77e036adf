"""Epoch-executor tests (DESIGN.md section 9): vectorized packing oracle,
in-jit plan batches vs the host packer, scan-vs-per-step-loop numerical
equivalence, tail-batch padding semantics, and single-vs-multi-device
shard_map parity (natively when >= 2 devices exist -- the CI tier-1 matrix
2-device entry -- and via an XLA_FLAGS subprocess everywhere else)."""
import os
import subprocess
import sys
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.codebook import CodebookConfig
from repro.core.conv import init_layer_vq_state
from repro.graph.batching import (build_epoch_plan, epoch_slices,
                                  full_operands, make_pack, minibatch_stream,
                                  plan_batch)
from repro.graph.datasets import synthetic_arxiv
from repro.graph.structure import CSR
from repro.models.gnn import (GNNConfig, init_gnn, init_vq_states,
                              vq_train_epoch, vq_train_step)
from repro.train.optimizer import rmsprop

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
PACK_FIELDS = ("batch_ids", "nbr_ids", "nbr_mask", "nbr_pos",
               "rev_ids", "rev_mask", "rev_pos")


def _copy(tree):
    """vq_train_epoch donates its carry buffers; tests that reuse the same
    initial state across paths must hand each call its own copy."""
    return jax.tree_util.tree_map(lambda a: a.copy(), tree)


@pytest.fixture(scope="module")
def g():
    return synthetic_arxiv(n=300, seed=0)


@pytest.fixture(scope="module")
def setup(g):
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=32,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=32, f_prod=4))
    ops = full_operands(g)
    tm = np.zeros(g.n, np.float32)
    tm[g.train_idx] = 1.0
    params = init_gnn(jax.random.PRNGKey(0), cfg)
    vq = init_vq_states(jax.random.PRNGKey(1), cfg, g.n)
    opt = rmsprop(3e-3)
    return dict(cfg=cfg, ops=ops, x=jnp.asarray(g.features),
                labels=jnp.asarray(g.labels), tm_np=tm,
                tm=jnp.asarray(tm), params=params, vq=vq, opt=opt,
                ost=opt.init(params), plan=build_epoch_plan(g))


# ---------------------------------------------------------------------------
# packing layer
# ---------------------------------------------------------------------------

def test_vectorized_pack_rows_matches_loop_reference(g):
    """The CSR-sliced _pack_rows equals the per-row reference on real and
    degree-capped rows."""
    from repro.graph.batching import _pack_rows
    rng = np.random.default_rng(3)
    ids = rng.permutation(g.n)[:64]
    inv = np.full(g.n, -1, np.int32)
    inv[ids] = np.arange(len(ids), dtype=np.int32)
    for csr, cap in [(g.in_csr, g.max_degree()), (g.out_csr, 3)]:
        nbr, mask, pos = _pack_rows(csr, ids, cap, inv)
        for r, i in enumerate(ids):
            ns = csr.neighbors(i)[:cap]
            d = len(ns)
            assert np.array_equal(nbr[r, :d], ns)
            assert np.all(nbr[r, d:] == 0)
            assert np.all(mask[r, :d] == 1.0) and np.all(mask[r, d:] == 0)
            assert np.array_equal(pos[r, :d], inv[ns])
            assert np.all(pos[r, d:] == -1)


def test_pack_rows_empty_graph():
    from repro.graph.batching import _pack_rows
    csr = CSR(indptr=np.zeros(5, np.int64), indices=np.zeros(0, np.int32))
    nbr, mask, pos = _pack_rows(csr, np.arange(4), 3, np.zeros(4, np.int32))
    assert nbr.shape == (4, 3) and not mask.any() and (pos == -1).all()


def test_plan_batch_matches_make_pack(g, setup):
    ids = np.random.default_rng(0).permutation(g.n)[:64]
    host = make_pack(g, ids)
    jit_pack = jax.jit(plan_batch)(setup["plan"],
                                   jnp.asarray(ids.astype(np.int32)))
    for name in PACK_FIELDS:
        assert np.array_equal(np.asarray(getattr(host, name)),
                              np.asarray(getattr(jit_pack, name))), name


def test_plan_batch_duplicate_ids_take_first_slot(g, setup):
    """Serve requests repeat ids: the host packer, the in-jit scatter and
    the sharded executor's sort-based positions all send a duplicated
    node's messages to its FIRST slot (the slot decides where a message
    enters the kernels' one-hot sums, so sharded serving stays
    bit-exact)."""
    from repro.graph.batching import _inbatch_positions
    rng = np.random.default_rng(5)
    ids = rng.permutation(g.n)[:48]
    ids = np.concatenate([ids, ids[rng.integers(0, 48, 16)]])
    rng.shuffle(ids)
    host = make_pack(g, ids)
    jit_pack = jax.jit(plan_batch)(setup["plan"],
                                   jnp.asarray(ids.astype(np.int32)))
    for name in PACK_FIELDS:
        assert np.array_equal(np.asarray(getattr(host, name)),
                              np.asarray(getattr(jit_pack, name))), name
    first = {}
    for s, i in enumerate(ids):
        first.setdefault(int(i), s)
    nbr, mask = np.asarray(jit_pack.nbr_ids), np.asarray(jit_pack.nbr_mask)
    want = np.where(mask != 0, np.vectorize(
        lambda i: first.get(int(i), -1))(nbr), -1)
    assert np.array_equal(np.asarray(jit_pack.nbr_pos), want)
    sorted_pos = _inbatch_positions(jnp.asarray(ids.astype(np.int32)),
                                    jit_pack.nbr_ids, jit_pack.nbr_mask)
    assert np.array_equal(np.asarray(sorted_pos), want)


# ---------------------------------------------------------------------------
# tail-batch padding (the old stream silently dropped up to b-1 nodes)
# ---------------------------------------------------------------------------

def test_epoch_slices_cover_pool_and_mask_padding():
    perm = np.random.default_rng(1).permutation(10)
    ids, smask = epoch_slices(perm, 4)
    assert ids.shape == (3, 4) and smask.shape == (3, 4)
    # every pool node appears among the unmasked slots exactly once
    real = ids[smask > 0]
    assert sorted(real.tolist()) == sorted(perm.tolist())
    # padding wraps to the start of the permutation and is masked
    assert np.array_equal(ids[-1, 2:], perm[:2])
    assert np.array_equal(smask[-1], [1, 1, 0, 0])


def test_epoch_slices_pool_smaller_than_batch():
    """batch_size clamps to the pool: one duplicate-free unpadded batch
    (duplicate ids inside a batch would corrupt the refresh counts)."""
    ids, smask = epoch_slices(np.asarray([7, 3]), 8)
    assert ids.shape == (1, 2)
    assert smask.sum() == 2.0
    assert sorted(ids[0].tolist()) == [3, 7]


def test_epoch_slices_batches_never_contain_duplicates():
    rng = np.random.default_rng(2)
    for n, b in [(10, 4), (10, 10), (10, 99), (7, 3), (300, 128)]:
        ids, smask = epoch_slices(rng.permutation(n), b)
        for row in ids:
            assert len(set(row.tolist())) == len(row), (n, b)


def test_minibatch_stream_traverses_all_nodes(g):
    rng = np.random.default_rng(0)
    seen = np.zeros(g.n, np.int64)
    n_batches = 0
    for pack in minibatch_stream(g, 128, rng):
        assert pack.slot_mask is not None
        bidx = np.asarray(pack.batch_ids)
        sm = np.asarray(pack.slot_mask)
        seen[bidx[sm > 0]] += 1
        n_batches += 1
    assert n_batches == -(-g.n // 128)     # ceil: the tail is not dropped
    assert (seen == 1).all()               # the node_loss freshness contract


# ---------------------------------------------------------------------------
# scan epoch vs per-step loop (fixed seed -> same states)
# ---------------------------------------------------------------------------

def test_scan_epoch_matches_per_step_loop(g, setup):
    s = setup
    bids, smask = epoch_slices(
        np.random.default_rng(7).permutation(g.n), 128)

    p_l, vq_l, o_l = _copy((s["params"], s["vq"], s["ost"]))
    for i in range(bids.shape[0]):
        pack = make_pack(g, bids[i], slot_mask=smask[i])
        lm = jnp.asarray(s["tm_np"][bids[i]] * smask[i])
        p_l, vq_l, o_l, _, _, _ = vq_train_step(
            p_l, vq_l, o_l, pack, s["x"][bids[i]], s["labels"][bids[i]],
            s["ops"].degrees, s["cfg"], s["opt"], loss_mask=lm)

    p_s, vq_s, o_s, losses, errs = vq_train_epoch(
        *_copy((s["params"], s["vq"], s["ost"])), s["plan"],
        jnp.asarray(bids.astype(np.int32)), jnp.asarray(smask), s["x"],
        s["labels"], s["tm"], s["ops"].degrees, s["cfg"], s["opt"])

    assert losses.shape == (bids.shape[0],)
    assert errs.shape == (bids.shape[0], s["cfg"].n_layers)
    for a, b in zip(jax.tree_util.tree_leaves((p_l, vq_l, o_l)),
                    jax.tree_util.tree_leaves((p_s, vq_s, o_s))):
        assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6)


def test_trainer_rejects_mesh_without_epoch_executor(g, setup, monkeypatch):
    """An explicit data-parallel request must never silently fall back to
    single-device training."""
    from repro.distributed.data_parallel import graph_dp_mesh
    from repro.train.gnn_trainer import train_vq
    monkeypatch.setenv("REPRO_EPOCH_EXECUTOR", "0")
    with pytest.raises(ValueError, match="epoch executor"):
        train_vq(g, setup["cfg"], epochs=1, batch_size=128,
                 mesh=graph_dp_mesh(1))


def test_trainer_evaluates_mesh_training_on_one_device(g, setup,
                                                       monkeypatch):
    """After a data-parallel epoch the params are replicated over the
    mesh; the full-graph evaluation must see them on x's one device (on
    a TPU, a jit over mesh-placed params would have XLA partition the
    Pallas kernels, which Mosaic refuses)."""
    from jax.sharding import SingleDeviceSharding
    from repro.distributed.data_parallel import graph_dp_mesh
    from repro.train import gnn_trainer
    seen = []
    evaluate = gnn_trainer._evaluate

    def spy(params, *args):
        seen.extend(leaf.sharding for leaf in jax.tree_util.tree_leaves(
            params))
        return evaluate(params, *args)

    monkeypatch.setattr(gnn_trainer, "_evaluate", spy)
    gnn_trainer.train_vq(g, setup["cfg"], epochs=1, batch_size=128,
                         mesh=graph_dp_mesh(1))
    assert seen and all(isinstance(s, SingleDeviceSharding) for s in seen)


def test_trainer_env_gate_paths_agree(g, setup, monkeypatch):
    """train_vq end-to-end: epoch executor (default) vs the
    REPRO_EPOCH_EXECUTOR=0 per-step fallback on the same seed."""
    from repro.train.gnn_trainer import train_vq
    cfg = setup["cfg"]
    monkeypatch.setenv("REPRO_EPOCH_EXECUTOR", "0")
    r_loop = train_vq(g, cfg, epochs=2, batch_size=128, eval_every=2)
    monkeypatch.setenv("REPRO_EPOCH_EXECUTOR", "1")
    r_scan = train_vq(g, cfg, epochs=2, batch_size=128, eval_every=2)
    for a, b in zip(jax.tree_util.tree_leaves(r_loop["params"]),
                    jax.tree_util.tree_leaves(r_scan["params"])):
        assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)
    assert r_loop["final"]["val"] == pytest.approx(
        r_scan["final"]["val"], abs=0.02)


# ---------------------------------------------------------------------------
# PRNG hygiene
# ---------------------------------------------------------------------------

def test_init_vq_state_key_is_split():
    """The codebook init and the random assignment must not consume the
    same key (the seed-repo bug reused it verbatim)."""
    key = jax.random.PRNGKey(5)
    cfg = CodebookConfig(k=16, f_prod=4)
    st = init_layer_vq_state(key, 50, 8, 8, cfg)
    reused = jax.random.randint(
        key, (st.codebook.n_branches, 50), 0, cfg.k).astype(jnp.int32)
    assert not np.array_equal(np.asarray(st.assignment), np.asarray(reused))


# ---------------------------------------------------------------------------
# shard_map data parallelism
# ---------------------------------------------------------------------------

def test_dp_single_device_mesh_matches_scan(g, setup):
    """ndev=1 instantiation of the dp executor == vq_train_epoch."""
    from repro.distributed.data_parallel import (graph_dp_mesh,
                                                 vq_train_epoch_dp)
    s = setup
    bids, smask = epoch_slices(
        np.random.default_rng(7).permutation(g.n), 128)
    bids_d = jnp.asarray(bids.astype(np.int32))
    smask_d = jnp.asarray(smask)
    args = (s["plan"], bids_d, smask_d, s["x"], s["labels"], s["tm"],
            s["ops"].degrees, s["cfg"], s["opt"])
    out_dp = vq_train_epoch_dp(graph_dp_mesh(1),
                               *_copy((s["params"], s["vq"], s["ost"])),
                               *args)
    out = vq_train_epoch(*_copy((s["params"], s["vq"], s["ost"])), *args)
    for a, b in zip(jax.tree_util.tree_leaves(out_dp[:4]),
                    jax.tree_util.tree_leaves(out[:4])):
        assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def test_dp_codebook_revival_identical_across_replicas():
    """Dead-codeword revival must pick replacement rows from the GLOBAL
    batch under data parallelism: the dead mask is replica-identical
    (psum'd sizes), so replica-local picks would silently diverge the
    'replicated' codebooks.  Exercised via the vmap collective oracle with
    an extreme revive threshold that marks every codeword dead."""
    from repro.core import codebook as cbm
    cfg = CodebookConfig(k=8, f_prod=4, revive_threshold=2.0)
    key = jax.random.PRNGKey(0)
    state = cbm.init_codebook(key, 8, 8, cfg)
    feats = jax.random.normal(key, (2, 16, 8))          # 2 replica shards
    grads = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
    new_state, _ = jax.vmap(
        lambda f, g: cbm.update(state, f, g, cfg, axis_name="i"),
        axis_name="i")(feats, grads)
    for leaf in jax.tree_util.tree_leaves(new_state):
        lanes = np.asarray(leaf)
        assert_allclose(lanes[0], lanes[1], rtol=0, atol=0)


def test_graph_dp_mesh_rejects_overprovisioning():
    from repro.distributed.sharding import graph_dp_mesh
    with pytest.raises(ValueError, match="device"):
        graph_dp_mesh(len(jax.devices()) + 1)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=2)")
def test_dp_two_device_mesh_matches_vmap_oracle(g, setup):
    """shard_map over a 2-device mesh == the same body under
    jax.vmap(axis_name=...): all cross-replica math (grad psum, codebook
    stats psum, assignment all_gather) agrees with the collective-free
    oracle."""
    from repro.distributed.data_parallel import (graph_dp_mesh,
                                                 vq_train_epoch_dp)
    from repro.models.gnn import _vq_epoch_body
    s = setup
    bids, smask = epoch_slices(
        np.random.default_rng(7).permutation(g.n), 128)
    bids_d = jnp.asarray(bids.astype(np.int32))
    smask_d = jnp.asarray(smask)
    out2 = vq_train_epoch_dp(
        graph_dp_mesh(2), *_copy((s["params"], s["vq"], s["ost"])),
        s["plan"], bids_d, smask_d, s["x"], s["labels"], s["tm"],
        s["ops"].degrees, s["cfg"], s["opt"])

    S, b = bids.shape
    bl = b // 2
    perm_sh = bids_d.reshape(S, 2, bl).transpose(1, 0, 2)
    sm_sh = smask_d.reshape(S, 2, bl).transpose(1, 0, 2)
    body = functools.partial(_vq_epoch_body, cfg=s["cfg"], opt=s["opt"],
                             axis_name="data")
    ref = jax.vmap(body, in_axes=(None, None, None, None, 0, 0,
                                  None, None, None, None),
                   axis_name="data")(
        *_copy((s["params"], s["vq"], s["ost"])), s["plan"], perm_sh,
        sm_sh, s["x"], s["labels"], s["tm"], s["ops"].degrees)
    for a, b_ in zip(jax.tree_util.tree_leaves(out2[:4]),
                     jax.tree_util.tree_leaves(ref[:4])):
        # vmap stacks the (identical) replicas; compare against lane 0
        assert_allclose(np.asarray(a), np.asarray(b_)[0],
                        rtol=5e-4, atol=1e-5)


@pytest.mark.skipif(len(jax.devices()) >= 2,
                    reason="runs natively on this host")
def test_dp_two_device_parity_subprocess():
    """Single-device hosts still exercise the 2-device parity: rerun the
    native test above in a subprocess with two virtual CPU devices (the
    XLA_FLAGS override must precede jax init, hence the fresh process)."""
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.abspath(__file__),
         "-k", "dp_two_device_mesh_matches_vmap_oracle"],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(SRC))
    assert "1 passed" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]

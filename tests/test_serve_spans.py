"""The program's own marks for the profiler: the three host spans that tile
``GNNServer.step`` and the ``jax.named_scope`` of each part of the VQ-GNN
layer in the lowered train and serve steps."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.codebook import CodebookConfig
from repro.distributed.sharding import graph_dp_mesh
from repro.graph.batching import build_epoch_plan, epoch_slices, full_operands
from repro.graph.datasets import synthetic_arxiv
from repro.launch.serve_gnn import GNNServer
from repro.models.gnn import (GNNConfig, init_gnn, init_vq_states,
                              vq_serve_batch, vq_train_epoch)
from repro.train.optimizer import rmsprop

SPANS = ("program.serve.put", "program.serve.dispatch",
         "program.serve.fetch")
SERVE_SCOPES = ("edge_norm", "in_batch", "context")
TRAIN_SCOPES = SERVE_SCOPES + ("vq_update", "optimizer")


@pytest.fixture(scope="module")
def g():
    return synthetic_arxiv(n=300, seed=0)


@pytest.fixture(scope="module")
def setup(g):
    cfg = GNNConfig(backbone="gcn", f_in=g.f, hidden=32,
                    n_out=g.num_classes, n_layers=2,
                    codebook=CodebookConfig(k=32, f_prod=4))
    ops = full_operands(g)
    return dict(cfg=cfg, ops=ops, x=jnp.asarray(g.features),
                params=init_gnn(jax.random.PRNGKey(0), cfg),
                vq=init_vq_states(jax.random.PRNGKey(1), cfg, g.n),
                plan=build_epoch_plan(g, full_ops=ops))


@pytest.mark.parametrize("sharded", [False, True])
def test_serve_step_spans_tile_every_step(g, setup, tmp_path, sharded):
    """Per step exactly one put, dispatch and fetch span, in that order and
    not overlapping, on the replicated and the row-sharded path alike."""
    s = setup
    kw = dict(mesh=graph_dp_mesh(1), shard_graph=True) if sharded else {}
    server = GNNServer(g, s["cfg"], s["params"], s["vq"], batch=64, **kw)
    server.warmup()
    ids = np.arange(64) * 3 % g.n
    steps = 3
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(steps):
            server.step(ids)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name in SPANS)
    assert [name for _, _, name in spans] == list(SPANS) * steps
    for (_, end, _), (start, _, _) in zip(spans, spans[1:]):
        assert end <= start


def _scopes(lowered) -> set:
    """Every component of the name stacks in the lowered module's
    locations."""
    text = lowered.as_text(debug_info=True)
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in re.split(r"[/()]", loc)}


def test_layer_parts_are_named_in_the_lowered_steps(g, setup):
    s = setup
    opt = rmsprop(3e-3)
    bids, sm = epoch_slices(np.arange(g.n), 128)
    train = _scopes(vq_train_epoch.lower(
        s["params"], s["vq"], opt.init(s["params"]), s["plan"],
        jnp.asarray(bids.astype(np.int32)), jnp.asarray(sm), s["x"],
        jnp.asarray(g.labels), jnp.ones(g.n), s["ops"].degrees, s["cfg"],
        opt))
    assert set(TRAIN_SCOPES) <= train
    serve = _scopes(vq_serve_batch.lower(
        s["params"], s["vq"], s["plan"], jnp.arange(64, dtype=jnp.int32),
        s["x"], s["ops"].degrees, s["cfg"]))
    assert set(SERVE_SCOPES) <= serve
    # serving updates neither the codebook nor the weights
    assert not {"vq_update", "optimizer"} & serve

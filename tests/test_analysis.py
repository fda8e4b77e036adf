"""repro.analysis checker tests: each rule must (a) stay silent on the
clean tree and (b) fire on a seeded regression -- a forced
dequant-before-kernel upcast, a dropped donation, a per-branch dispatch
explosion, an over-budget BlockSpec, a callback in a scan body, an env
read moved into a jit-reachable function, and so on.  The seeded
fixtures are the checker's own acceptance tests: a rule that cannot
catch its target regression is dead weight in CI."""
import ast
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import Finding, ast_checks, jaxpr_checks, \
    load_baseline, pallas_vmem, registry, suppress
from repro.kernels import ops

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SDS = jax.ShapeDtypeStruct


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Finding plumbing
# ---------------------------------------------------------------------------

def test_finding_formats_and_baseline(tmp_path):
    f = Finding("REPRO001", "src/repro/x.py", 7, "msg")
    assert f.format("text") == "src/repro/x.py:7: REPRO001 msg"
    assert f.format("github") == \
        "::error file=src/repro/x.py,line=7,title=REPRO001::msg"
    # line 0 findings still render a valid annotation line
    assert "line=1" in Finding("REPRO101", "<entry:e>", 0, "m").format(
        "github")
    base = tmp_path / "baseline.txt"
    base.write_text(f"# comment\n{f.key()}\n")
    keys = load_baseline(str(base))
    assert suppress([f], keys) == []
    other = Finding("REPRO002", "src/repro/x.py", 7, "msg")
    assert suppress([f, other], keys) == [other]


# ---------------------------------------------------------------------------
# AST rules on synthetic sources
# ---------------------------------------------------------------------------

def _sub_findings(src, rel):
    tree = ast.parse(textwrap.dedent(src))
    out = []
    out += ast_checks._banned_call_findings(rel, tree)
    out += ast_checks._kernel_loop_findings(rel, tree)
    out += ast_checks._pytree_findings(rel, tree)
    out += ast_checks._import_side_effect_findings(rel, tree)
    return out


def _env_findings(src, rel="src/repro/fake.py"):
    return ast_checks._env_findings([(rel, ast.parse(
        textwrap.dedent(src)))])


def test_repro001_env_read_in_jit_body():
    fs = _env_findings("""
        import os, jax
        @jax.jit
        def hot(x):
            return x * float(os.environ.get("SCALE", "1"))
    """)
    assert [f.rule for f in fs] == ["REPRO001"]


def test_repro001_transitive_reachability():
    # the env read sits in a helper the jit body merely references
    fs = _env_findings("""
        import os, jax
        def helper():
            return os.getenv("KNOB")
        @jax.jit
        def hot(x):
            return x if helper() else x
    """)
    assert [f.rule for f in fs] == ["REPRO001"]


def test_repro001_host_side_read_ok():
    # same read, but nothing jit-traced references the function
    fs = _env_findings("""
        import os
        def host_config():
            return os.environ.get("KNOB")
    """)
    assert fs == []


def test_repro001_scan_body_is_a_root():
    fs = _env_findings("""
        import os, jax
        def body(c, x):
            return c + float(os.environ.get("S", "0")), None
        def epoch(xs):
            return jax.lax.scan(body, 0.0, xs)
    """)
    assert [f.rule for f in fs] == ["REPRO001"]


def test_repro002_one_hot_in_hot_module():
    src = """
        import jax
        def assign_dense(idx, k):
            return jax.nn.one_hot(idx, k)
    """
    assert _rules(_sub_findings(src, "src/repro/core/codebook.py")) == \
        {"REPRO002"}
    # fine outside the hot modules
    assert _sub_findings(src, "src/repro/nn/ffn.py") == []


def test_repro002_one_hot_inside_kernel_body_allowed():
    """A one-hot block built inside a Pallas kernel body lives in VMEM
    for one tile; only the HBM-materialized indicator is banned."""
    src = """
        import jax
        def _kernel(ids_ref, src_ref, o_ref):
            a = jax.nn.one_hot(ids_ref[...], 256)
            o_ref[...] = a @ src_ref[...]
        def wrapper(ids, k):
            return jax.nn.one_hot(ids, k)
    """
    fs = _sub_findings(src, "src/repro/kernels/spmm_ell.py")
    assert [(f.rule, f.line) for f in fs] == [("REPRO002", 7)]


def test_repro002_einsum_scoping():
    src = """
        import jax.numpy as jnp
        def ctx(a, c):
            return jnp.einsum('nbk,nkf->nbf', a, c)
    """
    assert _rules(_sub_findings(src, "src/repro/core/conv.py")) == \
        {"REPRO002"}
    # the sketch-form einsum of message_passing.py stays sanctioned
    assert _sub_findings(src, "src/repro/core/message_passing.py") == []


def test_repro003_loop_in_kernel_body():
    src = """
        def _my_kernel(x_ref, o_ref):
            for i in range(4):
                o_ref[i] = x_ref[i]
    """
    assert _rules(_sub_findings(src, "src/repro/kernels/my.py")) == \
        {"REPRO003"}
    # host-side dispatch loops (no *_ref params) stay fine
    assert _sub_findings("""
        def _loop_fallback(ids, vals):
            return [vals[i] for i in range(3)]
    """, "src/repro/kernels/ops.py") == []


def test_repro004_unregistered_pytree():
    src = """
        class Box:
            def tree_flatten(self):
                return (self.a,), None
    """
    assert _rules(_sub_findings(src, "src/repro/graph/box.py")) == \
        {"REPRO004"}
    ok = """
        from jax.tree_util import register_pytree_node_class
        @register_pytree_node_class
        class Box:
            def tree_flatten(self):
                return (self.a,), None
    """
    assert _sub_findings(ok, "src/repro/graph/box.py") == []


def test_repro005_import_time_env_mutation():
    src = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_foo"
    """
    assert _rules(_sub_findings(src, "src/repro/launch/bad.py")) == \
        {"REPRO005"}
    guarded = """
        import os
        if __name__ == "__main__":
            os.environ["XLA_FLAGS"] = "--xla_foo"
    """
    assert _sub_findings(guarded, "src/repro/launch/dryrun.py") == []


# ---------------------------------------------------------------------------
# jaxpr rules on seeded regressions
# ---------------------------------------------------------------------------

def test_repro101_dispatch_count_regression():
    """Forcing the per-branch loop fallback explodes the pinned ONE
    context dispatch into one SpMM per branch."""
    ops.configure_context_dispatch(variant="loop")
    try:
        entry = registry._serve_entry("int8")
        findings = jaxpr_checks.check_entry(entry)
    finally:
        ops.configure_context_dispatch(reset=True)
    assert "REPRO101" in _rules(findings)


def test_repro102_callback_in_scan():
    def body_with_callback(x):
        def body(c, _):
            c = c + jax.pure_callback(
                lambda v: v, SDS(c.shape, c.dtype), c)
            return c, None
        out, _ = jax.lax.scan(body, x, None, length=2)
        return out

    entry = registry.Entry(
        name="fixture:callback",
        trace=lambda: jax.make_jaxpr(body_with_callback)(
            SDS((4,), jnp.float32)),
        lower=None)
    assert _rules(jaxpr_checks.check_entry(entry)) == {"REPRO102"}


def test_repro103_dequant_before_kernel():
    """Host-level int8 -> f32 upcast ahead of the kernel: both halves of
    the dtype-flow contract fire (storage dtype never reaches the
    kernel; an out-of-kernel convert_element_type dequantizes)."""
    def dequant_first(q, scale, idx, val):
        x = q.astype(jnp.float32) * scale  # the banned host dequant
        return ops.spmm_ell(idx, val, x)

    args = (SDS((64, 16), jnp.int8), SDS((1, 16), jnp.float32),
            SDS((8, 4), jnp.int32), SDS((8, 4), jnp.float32))
    entry = registry.Entry(
        name="fixture:dequant",
        trace=lambda: jax.make_jaxpr(dequant_first)(*args),
        lower=None, force_pallas=True,
        quantized_dtypes=(jnp.dtype(jnp.int8),))
    assert _rules(jaxpr_checks.check_entry(entry)) == {"REPRO103"}


def test_repro104_dropped_donation():
    def step(x):
        return x + 1.0

    arg = SDS((8, 8), jnp.float32)
    entry = registry.Entry(
        name="fixture:no-donate",
        trace=lambda: jax.make_jaxpr(step)(arg),
        lower=lambda: jax.jit(step).lower(arg),  # donate_argnums dropped
        donated_min=1)
    assert _rules(jaxpr_checks.check_entry(entry)) == {"REPRO104"}
    donating = registry.Entry(
        name="fixture:donate",
        trace=lambda: jax.make_jaxpr(step)(arg),
        lower=lambda: jax.jit(step, donate_argnums=(0,)).lower(arg),
        donated_min=1)
    assert jaxpr_checks.check_entry(donating) == []


def test_repro105_oversized_scan_carry():
    def epoch(table):  # [1024, 8] f32 = 32 KiB riding the carry
        def body(c, _):
            return c * 2.0, None
        out, _ = jax.lax.scan(body, table, None, length=3)
        return out

    entry = registry.Entry(
        name="fixture:big-carry",
        trace=lambda: jax.make_jaxpr(epoch)(SDS((1024, 8), jnp.float32)),
        lower=None, carry_budget=1024)
    assert _rules(jaxpr_checks.check_entry(entry)) == {"REPRO105"}


def test_repro106_dense_residual():
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        # saves the dense [b, Dr, f] reconstruction the lazy form avoids
        return x, jnp.broadcast_to(x[:, None, :], (16, 8, 8)) * 1.0

    def bwd(res, g):
        return (g + res.sum(1),)

    f.defvjp(fwd, bwd)
    _, vjp_fn = jax.vjp(f, jnp.ones((16, 8), jnp.float32))
    findings = jaxpr_checks.residual_leaf_findings(
        vjp_fn, 16 * 8 * 8 * 4, "<fixture>")
    assert _rules(findings) == {"REPRO106"}


def test_repro107_missing_counter_bump():
    entry = registry.Entry(
        name="fixture:no-bump",
        trace=lambda: jax.make_jaxpr(lambda x: x + 1.0)(
            SDS((4,), jnp.float32)),
        lower=None, counter="layer")
    assert _rules(jaxpr_checks.check_entry(entry)) == {"REPRO107"}


# ---------------------------------------------------------------------------
# VMEM rules on seeded regressions
# ---------------------------------------------------------------------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def test_repro201_over_budget_blockspec():
    # whole-array blocks: 16 MiB in + 16 MiB out, over the 16 MiB envelope
    def big(x):
        return pl.pallas_call(
            _copy_kernel, out_shape=SDS(x.shape, x.dtype),
            interpret=True)(x)

    cj = jax.make_jaxpr(big)(SDS((2048, 2048), jnp.float32))
    findings = pallas_vmem.check_dispatches(
        cj, "<fixture>", pallas_vmem._envelope_bytes(ops))
    assert _rules(findings) == {"REPRO201"}


def test_repro202_ragged_blockspec():
    def ragged(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(3,),
            in_specs=[pl.BlockSpec((4, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 8), lambda i: (i, 0)),
            out_shape=SDS(x.shape, x.dtype),
            interpret=True)(x)

    cj = jax.make_jaxpr(ragged)(SDS((10, 8), jnp.float32))
    findings = pallas_vmem.check_dispatches(
        cj, "<fixture>", pallas_vmem._envelope_bytes(ops))
    assert _rules(findings) == {"REPRO202"}


def test_repro203_forced_variant_mismatch():
    """Pinning the resident/fused variants past their crossovers is
    exactly the heuristic-vs-footprint mismatch the rule exists for."""
    ops.configure_spmm_dispatch(variant="resident")
    ops.configure_context_dispatch(variant="fused")
    try:
        findings = pallas_vmem._crossover_findings()
    finally:
        ops.configure_spmm_dispatch(reset=True)
        ops.configure_context_dispatch(reset=True)
    assert _rules(findings) == {"REPRO203"}
    spots = {f.path for f in findings}
    assert spots == {"<crossover:spmm_ell>", "<crossover:context_ell>"}


@pytest.mark.parametrize("nb,k,f_blk,deg,f_out,scaled", [
    (32, 1024, 4, 32, None, False),    # the paper's forward term
    (32, 1024, 4, 32, 128, False),     # its Eq. 7 term with W^T
    (8, 300, 5, 11, 128, True),        # int8 gradient codewords, ragged
    (1, 1, 1, 1, None, False),         # one word, one slot
])
def test_repro203_context_charge_covers_traced_footprint(nb, k, f_blk, deg,
                                                         f_out, scaled):
    """What the context dispatch charges (``context_ell.vmem_bytes``)
    covers the footprint pass 2 computes from the traced BlockSpecs: each
    block once there, double-buffered and tile-padded in the charge."""
    from repro.analysis.jaxpr_checks import pallas_calls
    from repro.kernels.context_ell import context_ell_pallas, vmem_bytes

    b = 130
    kw = {}
    if scaled:
        kw["cw_scale"] = SDS((nb, 1, f_blk), jnp.float32)
    if f_out is not None:
        kw["w_t"] = SDS((nb * f_blk, f_out), jnp.float32)
    names = list(kw)
    cj = jax.make_jaxpr(lambda i, v, a, c, *rest: context_ell_pallas(
        i, v, a, c, interpret=True, **dict(zip(names, rest))))(
            SDS((b, deg), jnp.int32), SDS((b, deg), jnp.float32),
            SDS((nb, 1000), jnp.int32),
            SDS((nb, k, f_blk), jnp.int8 if scaled else jnp.float32),
            *kw.values())
    (eqn,) = pallas_calls(cj)
    traced = pallas_vmem.dispatch_footprint(eqn)
    charged = vmem_bytes(nb, k, f_blk, deg, f_out=f_out, scaled=scaled)
    assert traced <= charged < 2.5 * traced


# ---------------------------------------------------------------------------
# the clean tree is exactly clean (the empty-baseline policy)
# ---------------------------------------------------------------------------

def test_ast_pass_clean_tree():
    assert ast_checks.run(ROOT) == []


def test_jaxpr_pass_clean_tree():
    assert jaxpr_checks.run() == []


def test_vmem_pass_clean_tree():
    assert pallas_vmem.run() == []


def test_registry_covers_all_tiers_and_both_widths():
    names = [e.name for e in registry.entries()]
    for tier in ops.PRECISIONS:
        label = "fp32" if tier == "fp32" else tier
        assert f"vq_infer_layer[{label}]" in names
        assert f"vq_serve_batch[{label}]" in names
    # branch-count invariance probes trace a second product-VQ width
    assert any("@f_prod=2" in n for n in names)
    for core in ("vq_train_epoch", "sampler_train_epoch"):
        assert core in names

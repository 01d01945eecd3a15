"""Algorithm 1 execution engine: split-composition equivalence, masked-scan
vs sliced-loop parity, gradient locality, classification server step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import lm_batch, tiny
from repro.core import lora as lora_lib
from repro.core import splitfl
from repro.models import build_model
from repro.optim import AdamW


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("granite-3-2b", n_layers=4)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)
    lora = model.init_lora(jax.random.PRNGKey(1))
    lora = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(7), x.shape) * 0.02, lora)
    return cfg, model, params, lora


@pytest.mark.parametrize("cut", [0, 1, 2, 3, 4])
def test_masked_scan_equals_sliced_all_cuts(setup, cut):
    cfg, model, params, lora = setup
    batch = lm_batch(cfg)
    # server side
    h_scan, _ = model.forward_hidden(params, lora, batch, cut=jnp.int32(cut),
                                     side="server", path="scan")
    h_sliced, _ = model.forward_hidden(params, lora, batch, cut=cut,
                                       side="server", path="sliced")
    np.testing.assert_allclose(np.asarray(h_scan), np.asarray(h_sliced),
                               atol=2e-5)


@pytest.mark.parametrize("cut", [1, 2, 3])
def test_split_composition_equals_full(setup, cut):
    """client(0:cut) -> activations -> server(cut:L) == full forward."""
    cfg, model, params, lora = setup
    batch = lm_batch(cfg)
    pc = dict(params)
    pc["layers"] = lora_lib.slice_stack(params["layers"], 0, cut)
    lc, _ = lora_lib.split_lora(lora, cut)
    v = splitfl.client_forward(model, pc, lc, batch, cut)
    loss_split, _ = splitfl.server_loss(model, params, lora, v, batch, cut)
    loss_full, _ = model.loss(params, lora, batch)
    np.testing.assert_allclose(float(loss_split), float(loss_full), rtol=1e-5)


def test_server_grads_localized(setup):
    """Server-side loss must produce ZERO gradient on client-side layers."""
    cfg, model, params, lora = setup
    cut = 2
    batch = lm_batch(cfg)
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 16, cfg.d_model))

    def loss_fn(lo):
        loss, _ = splitfl.server_loss(model, params, lo, v, batch, cut)
        return loss

    g = jax.grad(loss_fn)(lora)
    client_g, server_g = lora_lib.split_lora(g, cut)
    assert all(float(jnp.abs(x).max()) == 0.0
               for x in jax.tree.leaves(client_g)), "client-side grads leaked"
    assert any(float(jnp.abs(x).max()) > 0
               for x in jax.tree.leaves(server_g)), "server-side grads missing"


def test_activation_gradients_match_end_to_end(setup):
    """dv from the server step == d(full loss)/d(activations) at the cut."""
    cfg, model, params, lora = setup
    cut = 2
    batch = lm_batch(cfg)
    pc = dict(params)
    pc["layers"] = lora_lib.slice_stack(params["layers"], 0, cut)
    lc, _ = lora_lib.split_lora(lora, cut)
    v = splitfl.client_forward(model, pc, lc, batch, cut)

    dv_direct = jax.grad(
        lambda vv: splitfl.server_loss(model, params, lora, vv, batch, cut)[0])(v)

    opt = AdamW(1e-3)
    step = splitfl.make_server_step(model, opt, static_cut=cut, donate=False)
    _, _, _, dv_step = step(params, lora, opt.init(lora), v, batch)
    np.testing.assert_allclose(np.asarray(dv_direct), np.asarray(dv_step),
                               atol=1e-6)


def test_end_to_end_split_training_decreases_loss(setup):
    """A few Alg.1 rounds on one client must reduce the loss."""
    cfg, model, params, lora = setup
    cut = 2
    opt = AdamW(5e-3)
    batch = lm_batch(cfg, batch=4, seq=16, seed=3)
    pc = dict(params)
    pc["layers"] = lora_lib.slice_stack(params["layers"], 0, cut)
    lc, ls = lora_lib.split_lora(lora, cut)
    spec = jax.eval_shape(lambda: lora)
    ls_full = lora_lib.embed_in_full_shape(ls, spec, cut, "server")
    srv = splitfl.make_server_step(model, opt, static_cut=cut, donate=False)
    fwd, bwd = splitfl.make_client_step(model, opt, cut)
    so, co = opt.init(ls_full), opt.init(lc)
    losses = []
    for _ in range(8):
        v = fwd(pc, lc, batch)
        loss, ls_full, so, dv = srv(params, ls_full, so, v, batch)
        lc, co = bwd(pc, lc, co, batch, dv)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05, losses


def _cohort_state(model, params, lora, cuts, cfg, opt, *, with_head):
    """Per-client full-shape server adapters + opt states for a cohort."""
    spec = jax.eval_shape(lambda: lora)
    r = np.random.default_rng(0)
    loras, opts, vs, batches = [], [], [], []
    for cut in cuts:
        _, srv = lora_lib.split_lora(lora, cut)
        full = lora_lib.embed_in_full_shape(srv, spec, cut, "server")
        loras.append(full)
        if with_head:
            opts.append(opt.init({"lora": full, "head": params["cls_head"]}))
        else:
            opts.append(opt.init(full))
        vs.append(jnp.asarray(r.normal(size=(2, 16, cfg.d_model)), jnp.float32))
        batches.append(lm_batch(cfg, batch=2, seq=16, seed=cut))
    return loras, opts, vs, batches


# The cohort steps below reorder f32 reductions: vmap width, chunk size and
# the concatenated ragged batch each change how XLA sums, so results agree
# to rounding relative to their magnitude, not bit for bit.  Losses, dv and
# optimizer moments are plain f32 sums and hold to REL_SUM of each leaf's
# largest magnitude.
REL_SUM = 1e-5
# Updated adapters hold to REL_ADAPTER: AdamW's first step divides each
# gradient element by its own magnitude (m / (sqrt(v) + eps)), so an element
# whose gradient is near zero turns reduction-order noise into a step of up
# to lr.  Steps of lr=1e-3 on adapters of magnitude ~0.07 bound the
# difference near 2e-3 of that magnitude.
REL_ADAPTER = 2e-3
_ADAPTER_OUT = 1   # index of new_lora in the (loss, lora, opt, dv) outputs


def _assert_rel_close(want, got, rel):
    """|got - want| <= rel * max|want|, element-wise."""
    want, got = np.asarray(want), np.asarray(got)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * float(np.abs(want).max()))


def _assert_outputs_close(want, got):
    """Compare two server-step output tuples leaf by leaf (see REL_*)."""
    for i, (w, g) in enumerate(zip(want, got)):
        rel = REL_ADAPTER if i == _ADAPTER_OUT else REL_SUM
        for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(g)):
            _assert_rel_close(x, y, rel)


def test_batched_server_step_matches_sequential(setup):
    """ONE vmapped dispatch over the cohort == U sequential dispatches,
    for heterogeneous traced cuts (to the relative tolerances above)."""
    cfg, model, params, lora = setup
    opt = AdamW(1e-3)
    cuts = [1, 2, 3]
    loras, opts, vs, batches = _cohort_state(model, params, lora, cuts, cfg,
                                             opt, with_head=False)
    seq_losses, seq_loras = [], []
    for i, cut in enumerate(cuts):
        step = splitfl.make_server_step(model, opt, path="sliced",
                                        static_cut=cut, donate=False)
        loss, nl, _, dv = step(params, loras[i], opts[i], vs[i], batches[i])
        seq_losses.append(float(loss))
        seq_loras.append(nl)

    bstep = splitfl.make_server_step_batched(model, opt, donate=False)
    losses, nls, nos, dvs = bstep(
        params, lora_lib.stack_trees(loras), lora_lib.stack_trees(opts),
        jnp.stack(vs), lora_lib.stack_trees(batches), jnp.asarray(cuts))
    _assert_rel_close(seq_losses, losses, REL_SUM)
    for i in range(len(cuts)):
        for x, y in zip(jax.tree.leaves(seq_loras[i]),
                        jax.tree.leaves(lora_lib.unstack_tree(nls)[i])):
            _assert_rel_close(x, y, REL_ADAPTER)
    assert dvs.shape == (len(cuts),) + vs[0].shape


def test_batched_server_step_chunking_is_exact(setup):
    """cohort_chunk only changes dispatch granularity: chunk=1 (the paper's
    sequential server) == chunk=2 == one full chunk, up to f32 reduction
    order (the relative tolerances above)."""
    cfg, model, params, lora = setup
    opt = AdamW(1e-3)
    cuts = [1, 2, 3]
    loras, opts, vs, batches = _cohort_state(model, params, lora, cuts, cfg,
                                             opt, with_head=False)
    args = (params, lora_lib.stack_trees(loras), lora_lib.stack_trees(opts),
            jnp.stack(vs), lora_lib.stack_trees(batches), jnp.asarray(cuts))
    outs = [splitfl.make_server_step_batched(model, opt, cohort_chunk=k,
                                             donate=False)(*args)
            for k in (1, 2, None)]
    for other in outs[1:]:
        _assert_outputs_close(outs[0], other)


def test_batched_cls_server_step_matches_sequential():
    cfg = tiny("bert-base", n_layers=4)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    lora = model.init_lora(jax.random.PRNGKey(1))
    opt = AdamW(1e-2)
    cuts = [1, 2, 3]
    loras, opts, vs, batches = _cohort_state(model, params, lora, cuts, cfg,
                                             opt, with_head=True)
    heads = [params["cls_head"]] * len(cuts)
    seq = []
    for i, cut in enumerate(cuts):
        step = splitfl.make_server_step_cls(model, opt, path="sliced",
                                            static_cut=cut)
        seq.append(step(params, loras[i], heads[i], opts[i], vs[i], batches[i]))

    bstep = splitfl.make_server_step_cls_batched(model, opt, cohort_chunk=2)
    losses, nls, nhs, nos, dvs = bstep(
        params, lora_lib.stack_trees(loras), jnp.stack(heads),
        lora_lib.stack_trees(opts), jnp.stack(vs),
        lora_lib.stack_trees(batches), jnp.asarray(cuts))
    for i in range(len(cuts)):
        np.testing.assert_allclose(float(losses[i]), float(seq[i][0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(nhs[i]), np.asarray(seq[i][2]),
                                   atol=1e-5)
        for x, y in zip(jax.tree.leaves(lora_lib.unstack_tree(nls)[i]),
                        jax.tree.leaves(seq[i][1])):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_ragged_server_step_matches_vmap(setup):
    """impl="ragged" (cut-grouped concat batches, static cuts, layers
    [cut, L) only) == impl="vmap" (padded masked scan) for a mixed,
    unsorted cohort with duplicate cuts."""
    cfg, model, params, lora = setup
    opt = AdamW(1e-3)
    cuts = [3, 1, 3, 2]
    loras, opts, vs, batches = _cohort_state(model, params, lora, cuts, cfg,
                                             opt, with_head=False)
    args = (params, lora_lib.stack_trees(loras), lora_lib.stack_trees(opts),
            jnp.stack(vs), lora_lib.stack_trees(batches), jnp.asarray(cuts))
    out_v = splitfl.make_server_step_batched(model, opt, donate=False)(*args)
    out_r = splitfl.make_server_step_batched(model, opt, donate=False,
                                             impl="ragged")(*args)
    _assert_outputs_close(out_v, out_r)


def test_ragged_cls_server_step_matches_vmap():
    cfg = tiny("bert-base", n_layers=4)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    lora = model.init_lora(jax.random.PRNGKey(1))
    opt = AdamW(1e-2)
    cuts = [2, 3, 1, 2]
    loras, opts, vs, batches = _cohort_state(model, params, lora, cuts, cfg,
                                             opt, with_head=True)
    heads = [params["cls_head"]] * len(cuts)
    args = (params, lora_lib.stack_trees(loras), jnp.stack(heads),
            lora_lib.stack_trees(opts), jnp.stack(vs),
            lora_lib.stack_trees(batches), jnp.asarray(cuts))
    out_v = splitfl.make_server_step_cls_batched(model, opt)(*args)
    out_r = splitfl.make_server_step_cls_batched(model, opt,
                                                 impl="ragged")(*args)
    for x, y in zip(jax.tree.leaves(out_v), jax.tree.leaves(out_r)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)


def test_ragged_chunking_is_exact(setup):
    """cohort_chunk splits within a cut-group; numbers move only by f32
    reduction order (the relative tolerances above)."""
    cfg, model, params, lora = setup
    opt = AdamW(1e-3)
    cuts = [2, 2, 2, 1]
    loras, opts, vs, batches = _cohort_state(model, params, lora, cuts, cfg,
                                             opt, with_head=False)
    args = (params, lora_lib.stack_trees(loras), lora_lib.stack_trees(opts),
            jnp.stack(vs), lora_lib.stack_trees(batches), jnp.asarray(cuts))
    outs = [splitfl.make_server_step_batched(model, opt, donate=False,
                                             impl="ragged",
                                             cohort_chunk=k)(*args)
            for k in (1, None)]
    _assert_outputs_close(outs[0], outs[1])


def test_batched_step_rejects_unknown_impl(setup):
    cfg, model, params, lora = setup
    opt = AdamW(1e-3)
    with pytest.raises(KeyError):
        splitfl.make_server_step_batched(model, opt, impl="bogus")
    with pytest.raises(KeyError):
        splitfl.make_server_step_cls_batched(model, opt, impl="bogus")


def test_stack_unstack_roundtrip(setup):
    _, _, _, lora = setup
    trees = [jax.tree.map(lambda a, k=k: a + k, lora) for k in range(3)]
    back = lora_lib.unstack_tree(lora_lib.stack_trees(trees))
    for t, b in zip(trees, back):
        for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_classification_server_step(setup):
    cfg_cls = tiny("bert-base", n_layers=4)
    model = build_model(cfg_cls)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)
    lora = model.init_lora(jax.random.PRNGKey(1))
    batch = lm_batch(cfg_cls, batch=4, seq=16)
    cut = 1
    opt = AdamW(1e-2)
    step = splitfl.make_server_step_cls(model, opt, static_cut=cut)
    v = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, cfg_cls.d_model)),
                    jnp.float32)
    ost = opt.init({"lora": lora, "head": params["cls_head"]})
    loss, nl, nh, no, dv = step(params, lora, params["cls_head"], ost, v, batch)
    assert np.isfinite(float(loss))
    assert dv.shape == v.shape
    assert float(jnp.abs(nh - params["cls_head"]).max()) > 0  # head trains

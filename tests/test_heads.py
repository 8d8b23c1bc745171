"""Head-strategy API: every registered softmax head trains through the
head-agnostic hybrid trainer under identical conditions (the paper's §4.1
comparison as a parametrized test), and the full/knn heads match their
single-device oracles exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Experiment, HEAD_REGISTRY, make_head
from repro.api.heads import FullSoftmaxHead, HeadState
from repro.configs.base import HeadConfig, ModelConfig, TrainConfig
from repro.core import knn_graph as kg
from repro.core import knn_softmax as ks
from repro.core.sharded_softmax import ce_ref
from repro.data.synthetic import ClassificationStream, sku_feature_batch
from repro.telemetry import Tracer
from repro.train import hybrid

IMPLS = ["full", "knn", "selective", "mach", "sampled", "csoft"]
N, D, B = 256, 32, 64
LR = {"full": 4.0, "knn": 4.0, "selective": 4.0, "mach": 0.3,
      "sampled": 4.0, "csoft": 0.3}


def _model_cfg(n=N, d=D):
    return ModelConfig(name="feats", family="feats", n_layers=0, d_model=d,
                       n_heads=0, n_kv_heads=0, d_ff=0, vocab_size=n,
                       dtype="float32")


def _head_cfg(impl, **kw):
    kw.setdefault("active_frac", 0.5)
    kw.setdefault("knn_k", 8)
    kw.setdefault("knn_kprime", 16)
    return HeadConfig(softmax_impl=impl, **kw)


def test_registry_covers_paper_comparison():
    assert set(IMPLS) <= set(HEAD_REGISTRY)
    with pytest.raises(ValueError):
        make_head(_model_cfg(), HeadConfig(softmax_impl="bogus"))


def test_head_config_validation_names_registered_keys():
    """An unknown softmax_impl fails at HeadConfig construction with an
    error naming every registered head key."""
    with pytest.raises(ValueError) as exc:
        HeadConfig(softmax_impl="bogus")
    for key in IMPLS:
        assert key in str(exc.value)
    with pytest.raises(ValueError):
        HeadConfig(sampled_dist="zipfish")
    with pytest.raises(ValueError):
        HeadConfig(csoft_agg="max")


@pytest.mark.parametrize("impl", IMPLS)
def test_every_head_trains_on_hybrid_mesh(mesh8, impl):
    """Identical trainer, mesh, data and optimizer for all four heads: a few
    steps must produce finite, decreasing losses and a working eval path."""
    mcfg = _model_cfg()
    hcfg = _head_cfg(impl)
    tcfg = TrainConfig(optimizer="sgd", momentum=0.9)
    stream = ClassificationStream(N, D, seed=0)
    head = make_head(mcfg, hcfg)
    state = hybrid.init_state(jax.random.PRNGKey(0), mcfg, hcfg, tcfg, 8,
                              head=head)
    step = hybrid.make_train_step(mcfg, hcfg, tcfg, mesh8, head=head,
                                  state_template=state)
    with jax.set_mesh(mesh8):
        state = hybrid.refresh_head_state(head, mesh8, state)
        losses = []
        for t in range(10):
            state, loss, m = step(state, sku_feature_batch(t, B, stream),
                                  LR[impl])
            losses.append(float(loss))
        ev = hybrid.make_eval_step(mcfg, hcfg, mesh8, state, head=head)
        acc = float(ev(state, sku_feature_batch(10**6, 2 * B, stream)))
    assert all(jnp.isfinite(jnp.asarray(losses))), losses
    assert losses[-1] < losses[0], losses
    assert 0.0 <= acc <= 1.0
    for key in head.metrics_spec():
        assert key in m


@pytest.fixture(scope="module")
def small_problem():
    key = jax.random.PRNGKey(3)
    kf, ky = jax.random.split(key)
    n, d, b = 64, 32, 16
    f = jax.random.normal(kf, (b, d), jnp.float32)
    y = jax.random.randint(ky, (b,), 0, n)
    return n, d, f, y


def _first_step_loss(mesh8, impl, small_problem, **hkw):
    n, d, f, y = small_problem
    mcfg = _model_cfg(n, d)
    hcfg = _head_cfg(impl, **hkw)
    tcfg = TrainConfig(optimizer="sgd", momentum=0.0)
    head = make_head(mcfg, hcfg)
    state = hybrid.init_state(jax.random.PRNGKey(0), mcfg, hcfg, tcfg, 8,
                              head=head)
    step = hybrid.make_train_step(mcfg, hcfg, tcfg, mesh8, head=head,
                                  state_template=state)
    with jax.set_mesh(mesh8):
        state = hybrid.refresh_head_state(head, mesh8, state)
        w0 = jax.device_get(state.head_params)
        _, loss, _ = step(state, {"features": f, "labels": y}, 0.0)
    return float(loss), jnp.asarray(w0)


def test_full_head_matches_ce_ref(mesh8, small_problem):
    """Distributed full-softmax loss == single-device oracle."""
    n, d, f, y = small_problem
    loss, w0 = _first_step_loss(mesh8, "full", small_problem)
    loss_ref, _ = ce_ref(f, y, w0, cosine_scale=16.0)
    assert abs(loss - float(loss_ref)) < 1e-4


def test_knn_head_matches_oracle(mesh8, small_problem):
    """With every candidate kept (m_local = V_loc, no random padding) the
    distributed KNN-softmax loss equals the single-device oracle on the
    exact graph."""
    n, d, f, y = small_problem
    loss, w0 = _first_step_loss(mesh8, "knn", small_problem,
                                active_frac=1.0, knn_pad_random=False)
    graph = kg.knn_graph_ref(w0, 8)
    loss_ref = ks.knn_softmax_ref(f, y, w0, graph, m=min(f.shape[0] * 8, n),
                                  cosine_scale=16.0)
    assert abs(loss - float(loss_ref)) < 1e-4


def test_refresh_is_noop_for_heads_without_periodic_work(mesh8):
    """rebuild_every only drives heads that HAVE periodic work; for the
    others refresh must be an identity (the launch-shim regression)."""
    mcfg = _model_cfg()
    for impl, has_work in (("full", False), ("knn", True),
                           ("selective", True), ("mach", False),
                           ("sampled", False), ("csoft", False)):
        hcfg = _head_cfg(impl, rebuild_every=100)
        head = make_head(mcfg, hcfg)
        assert head.refresh_every == (100 if has_work else 0), impl
        if not has_work:
            hs = head.init(jax.random.PRNGKey(0), 8)
            hs2 = head.refresh(mesh8, hs, model_axis=hybrid.AXIS)
            assert hs2 is hs


def test_sampled_loss_approaches_full_softmax(mesh8, small_problem):
    """The logQ-corrected sampled loss converges to the full-softmax loss
    as the sample count approaches the class count, matching it EXACTLY at
    full draw (uniform mode samples per-shard without replacement)."""
    n, d, f, y = small_problem
    diffs = []
    for m in (n // 4, n // 2, n):
        loss, w0 = _first_step_loss(mesh8, "sampled", small_problem,
                                    sampled_n=m)
        loss_ref, _ = ce_ref(f, y, jnp.asarray(w0), cosine_scale=16.0)
        diffs.append(abs(loss - float(loss_ref)))
    assert diffs[-1] < 1e-3, diffs
    assert diffs[0] > diffs[1] > diffs[2], diffs


def test_sampled_log_uniform_trains(mesh8):
    """The Zipfian (with-replacement, shared-draw) sampler also trains:
    finite decreasing losses and fresh negatives every step."""
    mcfg = _model_cfg()
    hcfg = _head_cfg("sampled", sampled_dist="log_uniform", sampled_n=128)
    tcfg = TrainConfig(optimizer="sgd", momentum=0.9)
    stream = ClassificationStream(N, D, seed=0)
    head = make_head(mcfg, hcfg)
    state = hybrid.init_state(jax.random.PRNGKey(0), mcfg, hcfg, tcfg, 8,
                              head=head)
    step = hybrid.make_train_step(mcfg, hcfg, tcfg, mesh8, head=head,
                                  state_template=state)
    with jax.set_mesh(mesh8):
        losses = []
        for t in range(8):
            state, loss, m = step(state, sku_feature_batch(t, B, stream),
                                  4.0)
            losses.append(float(loss))
    assert all(jnp.isfinite(jnp.asarray(losses))), losses
    assert losses[-1] < losses[0], losses
    assert 0.0 < float(m["sample_frac"]) <= 1.0


def test_csoft_decode_roundtrips_labels(mesh8):
    """Count-min decode: encode each class's centroid into the sketch
    (bucket weight = superposition of the centroids hashing there), then
    the min-aggregated distributed decode recovers the class with high
    top-1 recovery on a small vocabulary."""
    n, d = 64, 32
    mcfg = _model_cfg(n, d)
    tcfg = TrainConfig(optimizer="sgd", momentum=0.0)
    cent = jax.random.normal(jax.random.PRNGKey(7), (n, d), jnp.float32)
    cent = cent / jnp.linalg.norm(cent, axis=-1, keepdims=True)
    for agg in ("min", "mean"):
        hcfg = _head_cfg("csoft", csoft_b=32, csoft_r=4, csoft_agg=agg)
        head = make_head(mcfg, hcfg)
        state = hybrid.init_state(jax.random.PRNGKey(0), mcfg, hcfg, tcfg,
                                  8, head=head)
        hashes = jnp.asarray(jax.device_get(state.head_aux[0]))  # [R, N]
        w = jnp.zeros(state.head_params.shape, jnp.float32)
        for r in range(hashes.shape[0]):
            w = w.at[r].set(w[r].at[hashes[r]].add(cent) * 16.0)
        state = state._replace(head_params=w)
        ev = hybrid.make_eval_step(mcfg, hcfg, mesh8, state, head=head)
        with jax.set_mesh(mesh8):
            acc = float(ev(state, {"features": cent,
                                   "labels": jnp.arange(n)}))
        assert acc >= 0.9, (agg, acc)


@pytest.mark.parametrize("impl", ["knn", "sampled", "csoft"])
def test_zoo_experiment_any_registry_head(impl):
    """ZooExperiment routes its loss through the head registry: graph-
    carrying, W-sampling and sketch heads all train + evaluate on the
    GSPMD mesh with no trainer changes."""
    kw = {"knn": dict(knn_k=8, active_frac=0.5, rebuild_every=2),
          "sampled": dict(sampled_n=256),
          "csoft": dict(csoft_b=64, csoft_r=2)}[impl]
    exp = Experiment.from_config(
        system="zoo", arch="smollm_135m", reduced=True, batch=8, seq=32,
        head=HeadConfig(softmax_impl=impl, **kw), log_every=0)
    hist = exp.fit(3, lr=0.2)
    assert len(hist) == 3
    assert all(jnp.isfinite(jnp.asarray([r["loss"] for r in hist])))
    acc = exp.evaluate()
    assert 0.0 <= acc <= 1.0


def test_zoo_registry_parity_with_hybrid(mesh8, mesh2x4, par2x4):
    """Same head (mach), same FE/head init keys, same repeated batch: the
    registry-routed zoo step and the hybrid trainer produce comparable
    decreasing loss trajectories (different meshes, same math)."""
    from jax.sharding import NamedSharding

    from repro.configs.base import InputShape
    from repro.data.synthetic import lm_batch
    from repro.models import lm
    from repro.optim import make_optimizer
    from repro.train import gspmd
    from tests.conftest import reduced_cfg

    cfg = reduced_cfg("smollm_135m")
    hcfg = HeadConfig(softmax_impl="mach", mach_b=64, mach_r=2)
    tcfg = TrainConfig(optimizer="sgd", momentum=0.0)
    inputs = lm_batch(0, 16, 32, cfg.vocab_size)
    steps, lr = 4, 0.2

    head = make_head(cfg, hcfg)
    state = hybrid.init_state(jax.random.PRNGKey(0), cfg, hcfg, tcfg, 8,
                              head=head)
    step = hybrid.make_train_step(cfg, hcfg, tcfg, mesh8, head=head,
                                  state_template=state)
    losses_h = []
    with jax.set_mesh(mesh8):
        for _ in range(steps):
            state, loss, _ = step(state, inputs, lr)
            losses_h.append(float(loss))

    # zoo side with the SAME init keys hybrid.init_state used
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    head_z = make_head(cfg, hcfg)
    with jax.set_mesh(mesh2x4):
        params = lm.init_model(k1, cfg)
        params = jax.tree.map(jax.device_put, params,
                              gspmd.param_shardings(cfg, par2x4, mesh2x4))
        hs = head_z.init(k2, 4)   # mach_b=64 divides 8 and 4: same arrays

        def put(tree, spec):
            return jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh2x4, s)),
                tree, spec)

        hstate = HeadState(put(hs.params, head_z.params_spec("model")),
                           put(hs.aux, head_z.aux_spec("model")))
        opt_state = make_optimizer(tcfg).init((params, hstate.params))
        zstep = jax.jit(gspmd.make_head_train_step(
            cfg, hcfg, par2x4, tcfg, mesh2x4,
            InputShape("t", 32, 16, "train"), head=head_z))
        losses_z = []
        for _ in range(steps):
            params, hstate, opt_state, loss, _ = zstep(
                params, hstate, opt_state, inputs, lr)
            losses_z.append(float(loss))

    assert losses_h[-1] < losses_h[0], losses_h
    assert losses_z[-1] < losses_z[0], losses_z
    # identical starting loss (same init, same math) ...
    assert abs(losses_h[0] - losses_z[0]) < 1e-3, (losses_h, losses_z)
    # ... and comparable descent after updates (hybrid's dense_exchange
    # averages FE grads over the ring, so the paths drift slightly)
    for a, b in zip(losses_h, losses_z):
        assert abs(a - b) < 0.15 * losses_h[0], (losses_h, losses_z)


def test_paper_experiment_facade(mesh8):
    """Experiment.from_config -> fit/evaluate/serve, end to end."""
    exp = Experiment.from_config(
        system="paper", classes=N, feat_dim=D, batch=B, mesh=mesh8,
        head=_head_cfg("knn", rebuild_every=0), log_every=0)
    hist = exp.fit(8, use_fccs_batch=False)
    assert len(hist) == 8
    assert hist[-1]["loss"] < hist[0]["loss"]
    acc = exp.evaluate()
    assert 0.0 <= acc <= 1.0
    preds = exp.serve(batch=B)
    assert preds.shape == (B,)
    assert preds.dtype == jnp.int32


# ---------------------------------------------------------------------------
# the head's per-update transform, once per update (prepare_params)
# ---------------------------------------------------------------------------


class _PerMicroBatch:
    """The head with its whole ``loss_local`` inside every micro-batch: the
    identity transform, and the untransformed body. Everything else is the
    wrapped head's."""

    def __init__(self, head):
        self._head = head

    def __getattr__(self, name):
        return getattr(self._head, name)

    def prepare_params(self, params):
        return params

    def loss_prepared(self, *args, **kwargs):
        return self._head.loss_local(*args, **kwargs)


PREP_N, PREP_B = 512, 64


def _prepared_problem(mesh, impl, backend, cosine_scale):
    mcfg = _model_cfg(PREP_N)
    hcfg = _head_cfg(impl, backend=backend, cosine_scale=cosine_scale,
                     knn_k=8, knn_kprime=16, active_frac=0.2, sampled_n=128,
                     mach_b=32, csoft_b=32)
    tcfg = TrainConfig(optimizer="sgd", momentum=0.9)
    head = make_head(mcfg, hcfg)
    with jax.set_mesh(mesh):
        state = hybrid.place_state(hybrid.init_state(
            jax.random.PRNGKey(5), mcfg, hcfg, tcfg, 8, head=head),
            head, mesh)
        state = hybrid.refresh_head_state(head, mesh, state)
    batch = sku_feature_batch(0, PREP_B, ClassificationStream(PREP_N, D,
                                                              seed=2))
    return mcfg, hcfg, tcfg, head, state, batch


def _step_both_ways(mesh, impl, backend, cosine_scale, n_micro):
    """One update with the transform out of the loop, and one with the
    per-micro-batch form, from the same state: (loss, accuracy, head
    gradient as the optimizer got it) for each."""
    mcfg, hcfg, tcfg, head, state, batch = _prepared_problem(
        mesh, impl, backend, cosine_scale)
    out = []
    for h in (head, _PerMicroBatch(head)):
        step = hybrid.make_train_step(mcfg, hcfg, tcfg, mesh,
                                      n_micro=n_micro, head=h,
                                      state_template=state)
        with jax.set_mesh(mesh):
            new, loss, metrics = step(state, batch, 0.5)
            out.append((float(loss), float(metrics["accuracy"]),
                        np.asarray(jax.device_get(new.opt_state.mu[1]))))
    return out


@pytest.mark.parametrize("cosine_scale", [16.0, 0.0])
@pytest.mark.parametrize("n_micro", [1, 4])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("impl", ["full", "knn"])
def test_prepare_out_of_loop_matches_per_micro_batch(mesh8, impl, backend,
                                                     n_micro, cosine_scale):
    """The class matrix normalized once per update gives the loss, accuracy
    and head gradient of normalizing it in every micro-batch, up to float
    reassociation of the normalization's gradient."""
    (l_a, acc_a, g_a), (l_b, acc_b, g_b) = _step_both_ways(
        mesh8, impl, backend, cosine_scale, n_micro)
    assert abs(l_a - l_b) <= 1e-6 * abs(l_b)
    assert acc_a == acc_b
    scale = np.max(np.abs(g_b))
    np.testing.assert_allclose(g_a, g_b, rtol=0, atol=1e-5 * scale)


@pytest.mark.parametrize("impl", ["selective", "sampled", "mach", "csoft"])
def test_other_heads_keep_the_identity_transform(mesh8, impl):
    """Heads whose bodies normalize what they gather keep the identity
    transform, inherited or not: the step's numbers are exactly those of
    ``loss_local`` in every micro-batch."""
    (l_a, acc_a, g_a), (l_b, acc_b, g_b) = _step_both_ways(
        mesh8, impl, "ref", 16.0, 4)
    head = make_head(_model_cfg(PREP_N), _head_cfg(impl))
    assert hybrid.head_prepare_passes(
        head, jnp.zeros((PREP_N, D), jnp.float32)) == 0
    assert (l_a, acc_a) == (l_b, acc_b)
    np.testing.assert_array_equal(g_a, g_b)


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(sub, "eqns"):
                yield sub
            elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                yield sub.jaxpr


def _eqns(jaxpr, in_loop=False):
    """Every equation of the jaxpr and of its sub-jaxprs (Pallas kernels
    left out), with whether it lies inside a loop's body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        if eqn.primitive.name == "pallas_call":
            continue
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in _subjaxprs(eqn):
            yield from _eqns(sub, loop)


def _w_row_reduces(jaxpr, w_shape):
    """(inside the loop, outside it) counts of row reductions of a
    [V_loc, D] array: a norm of W's rows, or that norm's gradient."""
    counts = [0, 0]
    for eqn, in_loop in _eqns(jaxpr):
        if (eqn.primitive.name == "reduce_sum"
                and tuple(eqn.params["axes"]) == (1,)
                and eqn.invars[0].aval.shape == w_shape):
            counts[0 if in_loop else 1] += 1
    return counts


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_head_prepare_runs_outside_the_micro_batch_loop(mesh8, backend):
    """In the step program of the full cosine head with 4 micro-batches,
    the ``head_prepare`` / ``head_prepare_bwd`` ops lie outside the scan's
    body and no row normalization of W is left inside it; the
    per-micro-batch form has it inside (the check can see it)."""
    mcfg, hcfg, tcfg, head, state, batch = _prepared_problem(
        mesh8, "full", backend, 16.0)
    w_shape = (PREP_N // 8, D)
    found = {}
    for name, h in (("out", head), ("in", _PerMicroBatch(head))):
        step = hybrid.make_train_step(mcfg, hcfg, tcfg, mesh8, n_micro=4,
                                      head=h, state_template=state)
        with jax.set_mesh(mesh8):
            jaxpr = jax.make_jaxpr(step)(state, batch, 0.5).jaxpr
        scopes = {"head_prepare": [0, 0], "head_prepare_bwd": [0, 0]}
        for eqn, in_loop in _eqns(jaxpr):
            stack = str(eqn.source_info.name_stack).split("/")
            for scope in scopes:
                if scope in stack:
                    scopes[scope][0 if in_loop else 1] += 1
        found[name] = (scopes, _w_row_reduces(jaxpr, w_shape))
    scopes, reduces = found["out"]
    assert scopes["head_prepare"][0] == scopes["head_prepare_bwd"][0] == 0
    assert scopes["head_prepare"][1] > 0 and scopes["head_prepare_bwd"][1] > 0
    assert reduces[0] == 0 and reduces[1] >= 2   # the norm and its gradient
    scopes, reduces = found["in"]
    assert reduces[0] >= 2
    assert scopes["head_prepare"][1] == scopes["head_prepare_bwd"][1] == 0


@pytest.mark.parametrize("backend,passes", [("pallas", 1), ("ref", 0)])
def test_knn_normalizes_the_whole_shard_on_pallas_only(mesh8, backend,
                                                       passes):
    """The knn pallas body scores against the whole normalized shard, so
    its normalization moves out of the loop; the ref body normalizes only
    the rows it gathers, so its step program holds no row reduction of the
    whole shard, inside the loop or out."""
    mcfg, hcfg, tcfg, head, state, batch = _prepared_problem(
        mesh8, "knn", backend, 16.0)
    assert hybrid.head_prepare_passes(head, state.head_params) == passes
    step = hybrid.make_train_step(mcfg, hcfg, tcfg, mesh8, n_micro=4,
                                  head=head, state_template=state)
    with jax.set_mesh(mesh8):
        jaxpr = jax.make_jaxpr(step)(state, batch, 0.5).jaxpr
    in_loop, out_loop = _w_row_reduces(jaxpr, (PREP_N // 8, D))
    assert in_loop == 0
    assert (out_loop >= 2) if passes else (out_loop == 0)


def test_head_overriding_loss_local_is_refused():
    """A head implements ``loss_prepared``; one that overrides
    ``loss_local`` would be bypassed by the hybrid trainer, so defining it
    fails."""
    with pytest.raises(TypeError, match="loss_prepared"):
        class _Bypassed(FullSoftmaxHead):
            def loss_local(self, *args, **kwargs):
                raise AssertionError("never called")


@pytest.mark.parametrize("cosine_scale,passes", [(16.0, 1), (0.0, 0)])
def test_head_prepare_passes_gauge(mesh8, cosine_scale, passes):
    """The trainer reports one whole-shard prepare pass per update for a
    cosine head, none for a raw one."""
    tr = Tracer()
    exp = Experiment.from_config(
        system="paper", classes=N, feat_dim=D, batch=B, mesh=mesh8,
        head=_head_cfg("full", cosine_scale=cosine_scale), log_every=0,
        telemetry=tr)
    exp.fit(1, use_fccs_batch=False)
    assert tr.gauges["train.head_prepare_passes"] == passes

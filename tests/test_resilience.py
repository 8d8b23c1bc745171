"""Fault-injection + resumable-training tests (docs/resilience.md).

The contract: a run killed mid-training and resumed by a FRESH trainer from
its latest full-state checkpoint is step-for-step equivalent to a run that
was never interrupted. Every head on the hybrid trainer (plus
full/knn/sampled/csoft on the zoo) recovers BITWISE on this container —
the data stream, FCCS schedule, and per-step sampling are pure functions
of the saved cursor, and XLA CPU execution is run-to-run deterministic.
``EQUIVALENCE`` below is the asserted class per head × backend; if a
future path loses determinism it must be downgraded HERE and in
docs/resilience.md, not silently.

Injection points exercised:
  * mid-epoch — kill between checkpoints; work since the last snapshot is
    lost and replayed from the restored cursor;
  * mid-refresh-interval — the knn/selective snapshot carries aux (graph /
    LSH tables) that is STALE relative to the params, exactly as the
    killed run's was; restore must not rebuild it;
  * post-DGC-accumulation — error-feedback residuals u/v are mid-flight
    and ride the snapshot;
  * straggler delay — numerics must be untouched; only wall-clock moves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt_lib
from repro.api import Experiment
from repro.configs.base import (DGCConfig, FCCSConfig, HeadConfig,
                                TrainConfig)
from repro.resilience import (FaultPlan, SimulatedFault, fault_hook,
                              kill_and_recover, tree_compare)

# the asserted recovery class per (head, backend) — see module docstring
EQUIVALENCE = {
    ("full", "ref"): "bitwise",
    ("knn", "ref"): "bitwise",
    ("selective", "ref"): "bitwise",
    ("mach", "ref"): "bitwise",
    ("sampled", "ref"): "bitwise",
    ("csoft", "ref"): "bitwise",
    ("full", "pallas"): "bitwise",
    ("knn", "pallas"): "bitwise",
}

ZOO_EQUIVALENCE = {
    "full": "bitwise", "knn": "bitwise",
    "sampled": "bitwise", "csoft": "bitwise",
}


def _head_cfg(head: str, backend: str = "ref") -> HeadConfig:
    # rebuild_every=5 with ckpt_every=4 and kill_at=6 puts the kill
    # mid-refresh-interval for knn/selective: the restored snapshot (step
    # 4) carries the PRE-refresh aux, and the refresh after replayed step 4
    # must rebuild the identical graph the killed run built.
    return HeadConfig(softmax_impl=head, backend=backend, knn_k=8,
                      knn_kprime=16, active_frac=0.25, rebuild_every=5,
                      sampled_n=64, mach_b=64, mach_r=2, csoft_b=64,
                      csoft_r=2)


def _paper_factory(tmp_path, head: str, backend: str = "ref",
                   dgc: bool = False, seed: int = 0):
    hcfg = _head_cfg(head, backend)
    tcfg = TrainConfig(
        optimizer="sgd",
        fccs=FCCSConfig(eta0=0.5, t_warm=2, b0=16, b_min=16, b_max=64,
                        t_ini=2, t_final=8),
        dgc=DGCConfig(enabled=dgc, sparsity=0.95, chunk=512))

    def make_exp(ckpt_dir):
        return Experiment.from_config(
            system="paper", classes=256, feat_dim=32, batch=16, head=hcfg,
            train=tcfg, ckpt_dir=ckpt_dir, ckpt_every=4, log_every=0,
            seed=seed)
    return make_exp


def _zoo_factory(tmp_path, head: str):
    hcfg = _head_cfg(head)

    def make_exp(ckpt_dir):
        return Experiment.from_config(
            system="zoo", arch="smollm_135m", reduced=True, head=hcfg,
            batch=8, seq=16, ckpt_dir=ckpt_dir, ckpt_every=2, log_every=0)
    return make_exp


# ---------------------------------------------------------------------------
# the headline matrix: kill mid-run, restore, assert equivalence class
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("head,backend", sorted(EQUIVALENCE))
def test_paper_kill_and_recover(head, backend, tmp_path, mesh8):
    make_exp = _paper_factory(tmp_path, head, backend)
    rep = kill_and_recover(
        make_exp, total_steps=8, kill_at=6, ckpt_dir=str(tmp_path / "ck"),
        equivalence=EQUIVALENCE[(head, backend)], head=f"{head}/{backend}",
        fit_kw={"use_fccs_batch": False})
    # kill at 6 with snapshots every 4: two steps of work lost and replayed
    assert rep.restored_step == 4 and rep.steps_replayed == 2
    assert rep.ok, rep.summary()


@pytest.mark.parametrize("head", sorted(ZOO_EQUIVALENCE))
def test_zoo_kill_and_recover(head, tmp_path):
    make_exp = _zoo_factory(tmp_path, head)
    rep = kill_and_recover(
        make_exp, total_steps=6, kill_at=5, ckpt_dir=str(tmp_path / "ck"),
        equivalence=ZOO_EQUIVALENCE[head], head=f"zoo/{head}",
        fit_kw={"lr": 0.5})
    assert rep.restored_step == 4 and rep.steps_replayed == 1
    assert rep.ok, rep.summary()


# ---------------------------------------------------------------------------
# injection-point specifics
# ---------------------------------------------------------------------------


def test_paper_kill_post_dgc_accumulation(tmp_path, mesh8):
    """DGC error-feedback residuals are mid-flight at the kill: they must
    ride the snapshot or the resumed gradient exchange diverges."""
    make_exp = _paper_factory(tmp_path, "full", dgc=True)
    rep = kill_and_recover(
        make_exp, total_steps=8, kill_at=6, ckpt_dir=str(tmp_path / "ck"),
        head="full+dgc", fit_kw={"use_fccs_batch": False})
    assert rep.ok, rep.summary()
    # the snapshot really carries the error-feedback buffers
    exp = make_exp(str(tmp_path / "ck"))
    tree = exp.trainer._snapshot()
    assert "dgc" in tree and set(tree["dgc"]) == {"u", "v"}


def test_paper_kill_mid_fccs_ramp(tmp_path, mesh8):
    """FCCS batch growth: the kill lands inside the cosine ramp, so the
    resumed run must pick up the SAME accumulation factor / batch size
    schedule from the cursor (a restart-from-zero would re-warm the LR and
    shrink the batch)."""
    make_exp = _paper_factory(tmp_path, "full")
    rep = kill_and_recover(
        make_exp, total_steps=8, kill_at=6, ckpt_dir=str(tmp_path / "ck"),
        head="full+fccs", fit_kw={"use_fccs_batch": True})
    assert rep.ok, rep.summary()
    # batch actually grew across the ramp in both runs
    batches = [r["batch"] for r in rep.reference_history]
    assert batches[-1] > batches[0]
    resumed = {r["step"]: r["batch"] for r in rep.resumed_history}
    for r in rep.reference_history:
        if r["step"] in resumed:
            assert resumed[r["step"]] == r["batch"]


def test_paper_delay_fault_is_numerically_invisible(tmp_path, mesh8):
    """A straggler delay must not perturb the trajectory — only time."""
    make_exp = _paper_factory(tmp_path, "full")
    ref = make_exp(None)
    ref.fit(4, use_fccs_batch=False)

    slept = []
    slow = make_exp(None)
    hook = fault_hook(FaultPlan(delay_at=2, delay_s=123.0),
                      sleep=slept.append)
    slow.fit(4, use_fccs_batch=False, step_hook=hook)
    assert slept == [123.0]
    cmp = tree_compare(slow.trainer._snapshot(), ref.trainer._snapshot())
    assert cmp["bitwise"], cmp["mismatches"]


# ---------------------------------------------------------------------------
# plumbing: facade resume, hook semantics, snapshot contract
# ---------------------------------------------------------------------------


def test_fit_resume_true_runs_only_the_tail(tmp_path, mesh8):
    make_exp = _paper_factory(tmp_path, "full")
    victim = make_exp(str(tmp_path / "ck"))
    with pytest.raises(SimulatedFault):
        victim.fit(8, use_fccs_batch=False,
                   step_hook=fault_hook(FaultPlan(kill_at=6)))

    resumed = make_exp(str(tmp_path / "ck"))
    hist = resumed.fit(8, use_fccs_batch=False, resume=True)
    # restored at 4 -> only steps 4..7 ran in this "process"
    assert [r["step"] for r in hist] == [4, 5, 6, 7]
    assert resumed.trainer._t == 8 and int(resumed.trainer.state.step) == 8
    # idempotent relaunch: target already reached -> no extra steps
    again = make_exp(str(tmp_path / "ck"))
    assert again.fit(8, use_fccs_batch=False, resume=True) == []


def test_fit_resume_without_checkpoint_is_cold_start(tmp_path, mesh8):
    make_exp = _paper_factory(tmp_path, "full")
    exp = make_exp(str(tmp_path / "empty"))
    hist = exp.fit(3, use_fccs_batch=False, resume=True)
    assert [r["step"] for r in hist] == [0, 1, 2]


def test_restore_without_ckpt_dir_raises(mesh8, tmp_path):
    exp = _paper_factory(tmp_path, "full")(None)
    with pytest.raises(ValueError, match="ckpt_dir"):
        exp.restore()


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="injects nothing"):
        FaultPlan()
    with pytest.raises(ValueError, match="delay_s"):
        FaultPlan(delay_at=1, delay_s=-1.0)
    with pytest.raises(ValueError, match="kill_at"):
        kill_and_recover(lambda d: None, total_steps=4, kill_at=0,
                         ckpt_dir="x")
    with pytest.raises(ValueError, match="equivalence"):
        kill_and_recover(lambda d: None, total_steps=4, kill_at=2,
                         ckpt_dir="x", equivalence="vibes")


def test_snapshot_contract_covers_head_aux(tmp_path, mesh8):
    """The checkpoint must include head-owned aux (the MACH lesson: sketch
    state is part of the model) — here the knn graph: restoring into a
    fresh trainer yields the SAME aux arrays even though the fresh
    trainer's warm-start graph has different shapes."""
    make_exp = _paper_factory(tmp_path, "knn")
    exp = make_exp(str(tmp_path / "ck"))
    exp.fit(6, use_fccs_batch=False)        # refresh fired at step 5
    exp.trainer.save_checkpoint()
    aux_before = [np.asarray(a) for a in exp.state.head_aux]

    fresh = make_exp(str(tmp_path / "ck"))
    fresh.restore()
    for a, b in zip([np.asarray(x) for x in fresh.state.head_aux],
                    aux_before):
        np.testing.assert_array_equal(a, b)
    assert fresh.trainer._t == 6


def test_step_hook_fires_before_the_step(tmp_path, mesh8):
    """Kill before step k leaves the state exactly at step k's entry: k
    steps taken, cursor k."""
    exp = _paper_factory(tmp_path, "full")(None)
    with pytest.raises(SimulatedFault):
        exp.fit(8, use_fccs_batch=False,
                step_hook=fault_hook(FaultPlan(kill_at=3)))
    assert exp.trainer._t == 3 and int(exp.trainer.state.step) == 3
    assert len(exp.trainer.history) == 3


# ---------------------------------------------------------------------------
# checkpoint layer: atomicity + retention
# ---------------------------------------------------------------------------


def test_checkpoint_write_is_atomic(tmp_path):
    import os
    path = str(tmp_path / "ck")
    ckpt_lib.save(path, {"x": jnp.arange(4.0)}, step=1)
    assert sorted(os.listdir(path)) == ["ckpt_1.msgpack.zst"]
    # overwrite same step: replaced, never duplicated / truncated
    ckpt_lib.save(path, {"x": jnp.arange(4.0) * 2}, step=1)
    tree, _ = ckpt_lib.restore(path, {"x": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(tree["x"]),
                                  [0.0, 2.0, 4.0, 6.0])
    assert not [f for f in os.listdir(path) if ".tmp" in f]


def test_checkpoint_retention_prunes_oldest_first(tmp_path):
    path = str(tmp_path / "ck")
    for s in (1, 5, 3, 9, 7):
        ckpt_lib.save(path, {"x": jnp.asarray(float(s))}, step=s, keep=3)
    assert ckpt_lib.all_steps(path) == [5, 7, 9]
    assert ckpt_lib.latest_step(path) == 9
    # prune() reports the doomed steps oldest-first
    ckpt_lib.save(path, {"x": jnp.asarray(0.0)}, step=11)
    assert ckpt_lib.prune(path, keep=2) == [5, 7]
    assert ckpt_lib.all_steps(path) == [9, 11]
    with pytest.raises(ValueError, match="keep"):
        ckpt_lib.prune(path, keep=0)


def test_checkpoint_keep_never_prunes_the_new_file(tmp_path):
    path = str(tmp_path / "ck")
    for s in range(6):
        ckpt_lib.save(path, {"x": jnp.asarray(float(s))}, step=s, keep=1)
        assert ckpt_lib.all_steps(path) == [s]


# ---------------------------------------------------------------------------
# compression-format compatibility (the hypothesis round-trip property test
# lives in tests/test_property.py; these regressions run without hypothesis)
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_mixed_dtypes_and_namedtuples(tmp_path):
    from repro.optim.optimizers import OptState
    tree = {
        "f32": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "f16": jnp.asarray([1.5, -2.25], jnp.float16),
        "i8": jnp.asarray([[-128, 127]], jnp.int8),
        "bool": jnp.asarray([True, False]),
        "empty": jnp.zeros((0, 4), jnp.float32),
        "scalar": jnp.asarray(7, jnp.int32),
        "opt": OptState(step=jnp.asarray(3, jnp.int32),
                        mu=({"w": jnp.ones((2,))}, ()), nu=None),
        "nested": [(), {"deep": (jnp.asarray(0.5),)}],
    }
    ckpt_lib.save(str(tmp_path), tree, step=9)
    out, step = ckpt_lib.restore(str(tmp_path), tree)
    assert step == 9
    fa = jax.tree_util.tree_flatten_with_path(tree)
    fb = jax.tree_util.tree_flatten_with_path(out)
    assert fa[1] == fb[1]
    for (pa, a), (_, b) in zip(fa[0], fb[0]):
        a, b = np.asarray(a), np.asarray(jax.device_get(b))
        assert a.dtype == b.dtype and a.shape == b.shape, pa
        assert a.tobytes() == b.tobytes(), pa


def test_zlib_written_checkpoint_restores_under_either_codec(tmp_path):
    """Checkpoints are zstd only: a file in another codec (zlib here) is
    refused with zstandard's frame error instead of being misread."""
    import zlib

    import zstandard
    tree = {"x": jnp.arange(8.0)}
    fname = ckpt_lib.save(str(tmp_path), tree, step=1)
    blob = open(fname, "rb").read()
    raw = zstandard.ZstdDecompressor().decompress(blob)
    open(fname, "wb").write(zlib.compress(raw))
    with pytest.raises(zstandard.ZstdError):
        ckpt_lib.restore(str(tmp_path), tree)


def test_zstd_written_checkpoint_roundtrips(tmp_path):
    from repro.checkpoint import checkpoint as mod
    tree = {"x": jnp.arange(8.0)}
    fname = ckpt_lib.save(str(tmp_path), tree, step=1)
    assert open(fname, "rb").read()[:4] == mod._ZSTD_MAGIC
    out, _ = ckpt_lib.restore(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(out["x"]), np.arange(8.0))


def test_zstd_checkpoint_without_zstandard_errors_clearly(tmp_path):
    """A truncated zstd frame fails loudly in the codec — not with an
    opaque msgpack decode error downstream."""
    import zstandard

    from repro.checkpoint import checkpoint as mod
    (tmp_path / "ckpt_5.msgpack.zst").write_bytes(
        mod._ZSTD_MAGIC + b"\x00" * 16)
    with pytest.raises(zstandard.ZstdError):
        ckpt_lib.restore(str(tmp_path), {"x": jnp.zeros(1)})

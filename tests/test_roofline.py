"""Roofline analysis layer: record analysis, MODEL_FLOPS, report rendering,
hillclimb knob parsing, mesh/parallel-config factories."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.hillclimb import parse_rules
from repro.configs.base import ParallelConfig, get_model_config
from repro.launch.mesh import make_host_parallel_config, make_parallel_config
from repro.roofline import analysis as an


def _fake_record(flops=1e12, nbytes=1e9, coll=1e7, mesh="16x16"):
    return {
        "arch": "smollm_135m", "shape": "train_4k", "mesh": mesh,
        "mode": "train", "knn": False, "n_params": 135_000_000,
        "memory": {"argument_bytes": 2 << 30, "output_bytes": 1 << 30,
                   "temp_bytes": 4 << 30, "peak_bytes": 5 << 30},
        "cost": {"flops": 1.0, "bytes_accessed": 1.0},
        "hlo": {"flops": flops, "bytes": nbytes},
        "collectives": {"total_bytes": coll},
    }


def test_analyze_record_terms():
    row = an.analyze_record(_fake_record())
    v5e = an.peaks("TPU v5 lite")
    assert row.compute_s == pytest.approx(1e12 / 197e12)
    assert row.memory_s == pytest.approx(1e9 / 819e9)
    assert row.collective_s == pytest.approx(1e7 / v5e["ici_bw"])
    assert row.dominant == "compute"
    assert row.n_chips == 256
    assert row.fits  # 2 + 5 GiB < 16


def test_analyze_record_dominance_switch():
    row = an.analyze_record(_fake_record(flops=1.0, nbytes=1e14))
    assert row.dominant == "memory"
    row = an.analyze_record(_fake_record(flops=1.0, coll=1e13))
    assert row.dominant == "collective"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        an.peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        an.analyze_record(dict(_fake_record(), device_kind="TPU v4"))


def test_analyze_record_skips_errors():
    assert an.analyze_record({"error": "boom"}) is None


def test_model_flops_regimes():
    cfg = get_model_config("smollm_135m")
    train = an.model_flops(cfg, "train_4k")
    prefill = an.model_flops(cfg, "prefill_32k")
    decode = an.model_flops(cfg, "decode_32k")
    # train >= 3x prefill-per-token (bwd) and decode << both
    assert train > 0 and prefill > 0 and decode > 0
    assert decode < prefill < train * 2
    # 6ND lower bound for train
    assert train >= 6 * 1.2e8 * 256 * 4096


def test_moe_active_params_lt_total():
    cfg = get_model_config("qwen3_moe_30b_a3b")
    import jax as _j

    from repro.models import lm
    sds = _j.eval_shape(lambda: lm.init_model(_j.random.PRNGKey(0), cfg))
    total = sum(l.size for l in _j.tree.leaves(sds))
    active = an.active_params(cfg)
    assert active < 0.3 * total  # top-8 of 128 experts


def test_markdown_render_and_hillclimb_mark():
    rows = [an.analyze_record(_fake_record())]
    md = an.to_markdown(rows, hillclimbed={("smollm_135m", "train_4k")})
    assert "**(hillclimbed)**" in md
    assert md.count("|") > 10


def test_parse_rules():
    assert parse_rules(["seq=model"]) == (("seq", "model"),)
    assert parse_rules(["vocab=data,model"]) == (("vocab", ("data", "model")),)
    assert parse_rules(["embed=none"]) == (("embed", None),)


def test_parallel_config_factories():
    p = make_parallel_config(multi_pod=True)
    assert p.axis_names == ("pod", "data", "model")
    assert p.batch_axes == ("pod", "data")
    assert p.mesh_axis_for_param("embed") == "data"   # FSDP
    assert p.mesh_axis_for("embed") is None           # activations unchanged
    p2 = make_parallel_config(fsdp=False)
    assert p2.param_rules is None
    ph = make_host_parallel_config(2, 4)
    assert ph.mesh_shape == (2, 4)


def test_rule_precedence_first_match_wins():
    p = ParallelConfig(mesh_shape=(2, 4), axis_names=("data", "model"),
                       rules=(("seq", "model"), ("seq", None)))
    assert p.mesh_axis_for("seq") == "model"

"""`chip_smoke.py` on CPU: the script refuses to run without a TPU, and
each of its phases runs end to end on small configurations (the phases
take their configuration as an argument; only the device check insists
on a TPU). Plus the compile-cache placement the script relies on."""
import importlib.util
import os

import jax
import pytest

from repro.configs import get_arch
from repro.configs.base import ShapeConfig, WirelessConfig

REPO = os.path.join(os.path.dirname(__file__), "..")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CS = _load_chip_smoke()
QWEN = get_arch("qwen1.5-0.5b").reduced()


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert CS.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "platform=cpu" in out


def test_main_refuses_path_switches(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_PREFILL_IMPL", "scan")
    assert CS.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_phase_serve_reduced():
    out = CS.phase_serve(QWEN, n_requests=6, n_slots=4, new_tokens=4,
                         prompt_lens=(4, 40))
    assert out["requests"] == 6 and out["tokens"] == 24
    # prompts cross several prefill buckets and several 16-token pages
    assert max(out["prompt_lens"]) > 32 and out["peak_pages"] > 4
    assert out["prefill_err"] <= CS.LOGIT_RTOL
    assert out["decode_err"] <= CS.LOGIT_RTOL


def test_phase_fl_paper_wire_kernel():
    out = CS.phase_fl_paper(
        WirelessConfig(mode="fl", use_kernel=True, snr_db=20.0, n_users=2,
                       local_steps=1),
        cycles=1, n_train=1024, n_test=512)
    assert out["params"] == 89_673
    assert out["bits"] == [2 * 89_673 * 8]


def test_phase_sl_scaled_reduced():
    out = CS.phase_sl_scaled(QWEN, ShapeConfig("t", 16, 4, "train"),
                             cycles=1)
    assert len(out["loss"]) == 1 and out["bits"][0] > 0


def test_phase_pod_fl_one_device_mesh():
    from repro.launch.mesh import make_mesh
    out = CS.phase_pod_fl(QWEN, ShapeConfig("t", 16, 2, "train"),
                          make_mesh((1, 1, 1), ("pod", "data", "model")),
                          cycles=1)
    assert out["loss"] == out["loss_one_chip"]


def test_compile_cache_placement(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins and is the only directory set;
    otherwise the fixed repo-local .jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch import compile_cache as CC
    assert CC.REPO_CACHE_DIR == os.path.abspath(
        os.path.join(REPO, ".jax_cache"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CC.cache_dir() == CC.REPO_CACHE_DIR
    d = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert CC.cache_dir() == d
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        assert CC.enable_persistent_cache() == d
        assert jax.config.jax_compilation_cache_dir == d
        assert os.path.isdir(d)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

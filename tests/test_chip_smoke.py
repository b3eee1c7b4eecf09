"""chip_smoke.py off the chip: it refuses without a TPU, and its
oracles and phase plumbing hold at tiny sizes on the CPU backend."""
from __future__ import annotations

import json

import pytest

import chip_smoke


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


@pytest.mark.parametrize("label,rung", [
    ("jitlin-tpu-matrix", "pallas-matrix"),
    ("jitlin-tpu-matrix-sharded", "sharded-matrix"),
    ("jitlin-tpu-frontier", "jitlin-device"),
    ("jitlin-tpu-batch", "batch"),
    ("jitlin-native", "native-c"),
    ("jitlin-cpu", "cpu"),
    ("jitlin-cpu(fallback)", "cpu"),
])
def test_rung_of_labels(label, rung):
    assert chip_smoke.rung_of(label) == rung


def test_host_labels_fail_the_device_check():
    for label in ("jitlin-cpu", "jitlin-native", "jitlin-cpu(fallback)"):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.require_device_label(label)
    chip_smoke.require_device_label("jitlin-cpu-matrix")  # CPU backend


def test_planted_read_is_the_cpu_oracles_failure():
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops

    good = chip_smoke.register_history(300, 3)
    bad, i = chip_smoke.plant_bad_read(good, len(good) // 2)
    assert good[i]["value"] != bad[i]["value"] == chip_smoke.NEVER_WRITTEN
    assert check_stream(encode_register_ops(good)).valid is True
    res = check_stream(encode_register_ops(bad))
    assert res.valid is False and bad[res.failed_op_index] is bad[i]


def test_keyed_history_plants_one_bad_key():
    from jepsen_tpu import independent
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    h = chip_smoke.keyed_history(3, 120, 5, bad_key=1)
    out = independent.checker(LinearizableChecker(accelerator="cpu")).check(
        {}, h, {})
    assert out["failures"] == ["1"]


@pytest.mark.parametrize("phase", [
    lambda: chip_smoke.phase_set_full(400),
    lambda: chip_smoke.phase_elle(600, 0),
    lambda: chip_smoke.phase_elle(600, 3),
], ids=["set_full", "elle", "elle_anomalous"])
def test_host_bound_phases_hold_to_their_oracles(phase):
    line = phase()
    json.dumps(line, default=str)
    assert line["cold_s"] >= 0 and line["warm_s"] >= 0


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache is
    the fixed <checkout>/.jax_cache."""
    import jax

    from jepsen_tpu import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent.joinpath(
            "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

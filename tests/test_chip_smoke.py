"""``chip_smoke.py``: its run function at a tiny cluster on the CPU, with the
Pallas kernels in the interpreter, and its refusal of any device but a TPU.
"""
import importlib.util
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_matches_sequential_reference_in_interpret_mode(chip_smoke):
    # 256 functions over 16 tags in waves of 512: more than 128 distinct
    # functions per wave, so the bulk kernel's row axis spans two tiles
    report = chip_smoke.run(workers=200, functions=256, tags=16, waves=2,
                            wave_size=512, tail=8, seed=5, interpret=True)
    assert chip_smoke.failures(report) == []
    assert report["mismatches"] == []
    assert report["stats"]["bulk_waves"] == 2
    assert report["stats"]["bulk_fallback"] == 0
    assert min(report["rows"]) > 128
    assert report["starts"].get("hot", 0) > 0  # the warmth column is live


def test_failures_reports_a_mismatch_and_a_fallback(chip_smoke):
    report = {"mismatches": [(0, 3, "f0001", "w00002", "w00007")],
              "stats": {"bulk_fallback": 1, "bulk_waves": 2}, "n_waves": 2}
    out = chip_smoke.failures(report)
    assert any("w00002" in line and "w00007" in line for line in out)
    assert any("bulk_fallback = 1" in line for line in out)


def test_main_refuses_a_cpu_device(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="needs a TPU.*cpu"):
        chip_smoke.main([])
    assert capsys.readouterr().out == ""  # no result line


def test_compile_cache_prefers_the_environment(tmp_path, monkeypatch):
    from repro.kernels.compile_cache import ENV, use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(ENV, str(tmp_path / "outside"))
        assert use_compile_cache(tmp_path / "fixed") == str(
            tmp_path / "outside")
        assert jax.config.jax_compilation_cache_dir == was  # untouched

        monkeypatch.delenv(ENV)
        assert use_compile_cache(tmp_path / "fixed") == str(
            tmp_path / "fixed")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

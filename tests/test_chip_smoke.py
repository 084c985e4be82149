"""chip_smoke.py's serving flow and checks, at a tiny size on the CPU.

The chip run uses the same code at MS MARCO widths; only main()'s device
check differs, and on the CPU it must refuse to report a result.
"""
import importlib.util
import pathlib
import sys

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TINY = dict(n_centroids=128, n_queries=16)


def test_one_chip_flow_passes_checks_a_to_c():
    """Cold pass, new generation, repeated pass: every ticket equals the
    direct engine, the Eq. 6 scores equal numpy, one hit per repeat."""
    out = chip_smoke.run(n_docs=384, chips=1, n_new=128, **TINY)
    assert out["tickets"] == 3 * TINY["n_queries"]
    assert out["cache_hits"] == TINY["n_queries"]
    assert out["precision"] == "float32"          # the CPU backend
    assert out["eq6_worst_per_term"] <= chip_smoke.EQ6_ATOL_PER_TERM


def test_sharded_flow_matches_per_shard_engine():
    """make_service on a one-device mesh against the per-shard engine, and
    every stacked index leaf placed on its mesh device."""
    corpus, first, _ = chip_smoke.make_data(256, 0, TINY["n_queries"], 0)
    index, meta = chip_smoke.build(corpus, 256, TINY["n_centroids"])
    out = chip_smoke.run_sharded(corpus, first, index, meta, 1,
                                 chip_smoke.FirstCalls())
    assert out["tickets"] == TINY["n_queries"]
    assert out["placed_leaves"] >= 12


def test_main_refuses_without_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "set"], ids=["default", "env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """No JAX_COMPILATION_CACHE_DIR: the fixed default directory. Set: the
    entry point leaves JAX's own reading of it alone."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    default = str(tmp_path / ".jax_cache")
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert use_compile_cache(default) == default
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert use_compile_cache(default) == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()

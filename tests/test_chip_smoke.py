"""chip_smoke.py rehearsed on CPU, and the compile-cache helper it calls.

The smoke's ``main`` runs in this process at a tiny size with interpret-mode
kernels: every phase must run and pass, and the last line must still say
``"ok": false`` — a CPU run can never pass as a chip run.
"""
import json

import jax

import chip_smoke


def test_chip_smoke_cpu_rehearsal_runs_every_phase(capsys):
    rc = chip_smoke.main([
        "--n", "600", "--clusters", "6", "--regions", "4", "--interpret",
        "--kernel-rows", "512", "--serve-n", "512", "--serve-clusters", "4",
        "--serve-dim", "8", "--requests", "3"])
    lines = capsys.readouterr().out.strip().splitlines()
    for i, name in enumerate(("kernels", "clustering", "serving"), start=2):
        verdicts = [ln for ln in lines if ln.startswith(f"[phase {i} {name}] ")]
        assert len(verdicts) == 1 and "PASS" in verdicts[0], (name, lines)
    assert any("Stage 1 engine: knn_topk pallas-interpret" in ln
               for ln in lines)
    assert any("Stage 3 engine: kmeans_iter pallas-interpret" in ln
               for ln in lines)
    last = json.loads(lines[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 1}}
    assert rc != 0


def test_chip_smoke_without_tpu_skips_the_full_size_run(capsys):
    assert chip_smoke.main([]) != 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert not any(ln.startswith("[phase 2") for ln in lines)
    assert json.loads(lines[-1])["ok"] is False


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch.cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # the variable wins, and nothing else is set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # otherwise the fixed directory inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

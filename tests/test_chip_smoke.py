"""CPU checks for ``chip_smoke.py`` and the bring-up repairs it depends on.

The smoke script's phases run here at smoke size (Pallas in interpret
mode), so the script cannot rot between chip runs; its entry point must
refuse a machine without a TPU; the serving engine must keep one weights
replica per device and pass them as executable arguments.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.core import get_all_devices

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_runtime_phase_matches_refs_at_smoke_size(chip_smoke):
    report = chip_smoke.runtime_phase(n=8192, stencil_block=2048)
    assert report["platforms"] == ["cpu"]
    for name in ("stencil", "partition_map"):
        k = report["kernels"][name]
        assert k["max_abs_err"] <= chip_smoke.KERNEL_ATOL
        assert k["tpu_custom_call"] is False  # interpret mode on the CPU


def test_serving_phase_matches_reference_at_smoke_size(chip_smoke):
    cfg = smoke(get_config("olmo-1b"))
    dev = get_all_devices().get()[:1]
    report = chip_smoke.serving_phase(cfg, dev, prompt_lens=(5, 17), new_tokens=4,
                                      max_seq_len=48, pool_bytes=1 << 20)
    eng = report["engine"]
    assert eng["requests_completed"] == chip_smoke.N_REQUESTS
    assert eng["requests_failed"] == 0
    ref = report["reference"]
    assert ref["max_choice_gap"] <= chip_smoke.CHOICE_MARGIN
    assert ref["prefill_max_abs_err"] <= chip_smoke.PREFILL_LOGIT_ATOL
    assert ref["argmax_agreement"] == 1.0  # float32 on both sides here


def test_main_refuses_a_machine_without_tpu(chip_smoke, capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_compile_cache_follows_env_or_the_checkout(monkeypatch, tmp_path):
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR == ROOT / ".jax_cache"
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_stencil_op_raises_on_a_shape_it_cannot_tile():
    from repro.kernels.stencil.ops import stencil

    with pytest.raises(ValueError, match="block"):
        stencil(np.ones(1000, np.float32))
    # the oracle stays reachable by asking for it
    np.testing.assert_allclose(np.asarray(stencil(np.ones(3, np.float32), impl="ref")),
                               [1.5, 2.0, 1.5])


def test_pallas_wrappers_require_an_explicit_interpret_flag():
    from repro.kernels.flash_attention.kernel import flash_attention_bhsd
    from repro.kernels.mandelbrot.kernel import mandelbrot
    from repro.kernels.paged_attention.kernel import paged_attention_bhd
    from repro.kernels.partition_map.kernel import partition_map
    from repro.kernels.ssd_scan.kernel import ssd_scan
    from repro.kernels.stencil.kernel import stencil

    x = np.ones(1024, np.float32)
    calls = [
        lambda: stencil(x, block=1024),
        lambda: partition_map(x, block=1024),
        lambda: mandelbrot(height=8, width=128, block=(8, 128)),
        lambda: flash_attention_bhsd(*(np.ones((1, 1, 128, 8), np.float32),) * 3),
        lambda: ssd_scan(*(np.ones((1, 8, 4), np.float32), np.ones((1, 8), np.float32),
                           np.ones((1,), np.float32), np.ones((1, 8, 2), np.float32),
                           np.ones((1, 8, 2), np.float32))),
        lambda: paged_attention_bhd(np.ones((1, 1, 8), np.float32),
                                    *(np.ones((2, 4, 1, 8), np.float32),) * 2,
                                    np.zeros((1, 1), np.int32), np.ones(1, np.int32)),
    ]
    for call in calls:
        with pytest.raises(TypeError, match="interpret"):
            call()


_FLEET_CHILD = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
    import importlib.util
    import jax, numpy as np
    from repro.configs import get_config, smoke
    from repro.core import get_all_devices
    from repro.models.model import get_model

    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    cfg = smoke(get_config("olmo-1b"))
    devs = get_all_devices().get()
    assert len(devs) == 4, devs
    rep = cs.fleet_phase(cfg, devs, prompt_lens=(5, 17), new_tokens=4,
                         max_seq_len=48, pool_bytes=1 << 20)
    print("STEPS", sorted(rep["decode_steps_by_device"].items()))

    # weights are executable arguments, never constants in the program
    params = jax.tree_util.tree_leaves(get_model(cfg).init(cfg, jax.random.PRNGKey(0)))
    eng, _, _ = cs.serve(cfg, None, devs, cs._prompts(cfg.vocab_size, (5,), 1), 2,
                         max_seq_len=48, pool_bytes=1 << 20)
    assert len(eng.weights) == 4
    w = eng.weights[devs[2].key]
    pool = eng.kv.pools[devs[2].key]
    ks, vs = pool.arrays()
    B = 2
    args = (w, ks, vs, None, np.zeros(B, np.int32), np.zeros(B, np.int32),
            np.zeros((B, eng.max_pages), np.int32), np.zeros(B, np.int32))
    lowered = eng.decode_fn.lower(*args)
    pre = eng.prefill_fn.lower(w, np.ones((1, 5), np.int32), None)
    for text in (lowered.as_text(), pre.as_text()):
        shapes = {"x".join(map(str, p.shape)) + "xf32" for p in params if p.ndim >= 2}
        consts = [l for l in text.splitlines() if "stablehlo.constant" in l
                  and any(f"tensor<{s}>" in l for s in shapes)]
        assert not consts, consts[:2]
    nbytes = sum(p.nbytes for p in params)
    assert lowered.compile().memory_analysis().argument_size_in_bytes >= nbytes
    print("FLEET_OK", rep["identical_tokens"])
    """
)


def test_fleet_phase_four_cpu_devices_one_replica_each_no_weight_constants():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _FLEET_CHILD, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FLEET_OK True" in proc.stdout, proc.stdout

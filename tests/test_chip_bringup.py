"""What the chip bring-up added to the program, checked without a chip:
the compile-cache helper, the peak table, the no-TPU refusals, the
launcher's one-process-per-chip rule, and the jax-free bench workers."""

import json
import os
import subprocess
import sys

import pytest

from _timing import scaled
from horovod_tpu import run as launcher
from horovod_tpu.utils import chip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


NAMES_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


@pytest.fixture()
def cache_dir_updates(monkeypatch):
    """Record what the helper would set instead of setting it: the suite
    itself must never run with a persistent compile cache."""
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    return updates


def test_compile_cache_honours_env(monkeypatch, cache_dir_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable; no directory is set, only what the key holds
    assert cache_dir_updates == [NAMES_IN_KEY]


def test_compile_cache_default_is_one_fixed_path(monkeypatch,
                                                 cache_dir_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = chip.enable_compile_cache(), chip.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert cache_dir_updates == [
        NAMES_IN_KEY, ("jax_compilation_cache_dir", first)] * 2


NAMED = """
import re, sys, jax, jax.numpy as jnp
from horovod_tpu.utils import chip
chip.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2
text = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
print(sorted(set(re.findall(r'op_name="jit.f./(\\w+)/', text))))
"""


def test_a_cached_program_comes_back_under_its_own_names(tmp_path):
    """Two programs of one arithmetic and two scopes, one cache: each
    compiled text carries its own scope (utils/profiling.scope_table reads
    it), not the one that was cached first."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    seen = [subprocess.run([sys.executable, "-c", NAMED, scope], env=env,
                           capture_output=True, text=True, timeout=300)
            for scope in ("hvd_before", "hvd_after", "hvd_before")]
    assert [p.stdout.strip() for p in seen] == [
        "['hvd_before']", "['hvd_after']", "['hvd_before']"], seen[1].stderr
    assert len(os.listdir(tmp_path)) >= 2       # and both were cached


def test_peak_table_raises_for_unknown_device_kind():
    assert chip.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="TPU v9000"):
        chip.peak_bf16_flops("TPU v9000")
    with pytest.raises(ValueError, match="cpu"):
        chip.peak_bf16_flops()  # the suite's own device is not a chip


def test_require_tpu_names_the_refused_phase():
    with pytest.raises(RuntimeError, match="some phase.*found none"):
        chip.require_tpu("some phase")


def test_chip_smoke_fails_without_a_tpu_and_prints_no_result():
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         capture_output=True, text=True, timeout=scaled(120))
    assert res.returncode != 0
    assert "found none" in res.stderr and "'cpu'" in res.stderr, res.stderr
    assert '"ok"' not in res.stdout, res.stdout


def _broken_leg(sz):
    raise ValueError("leg broke")


@pytest.mark.parametrize("legs, rc, ok", [("fine", 0, True),
                                          ("fine,broken", 1, False)])
def test_chip_smoke_last_stdout_line_is_the_result_object(
        legs, rc, ok, hvd, monkeypatch, capsys):
    """The driver reads the last line of stdout: one JSON object with
    exactly "ok" and "device" {"platform", "kind", "count"}."""
    import chip_smoke

    monkeypatch.setattr(chip, "require_tpu", lambda what: None)
    monkeypatch.setattr(chip, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(hvd, "shutdown", lambda: None)  # the session's
    monkeypatch.setattr(chip_smoke, "LEGS", {"fine": lambda sz: {},
                                             "broken": _broken_leg})
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--legs", legs])
    assert chip_smoke.main() == rc
    out, err = capsys.readouterr()
    result = json.loads(out.splitlines()[-1])
    assert result == {"ok": ok, "device": result["device"]}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert isinstance(result["device"]["count"], int)
    if not ok:
        assert "leg 'broken' FAILED" in err


@pytest.mark.parametrize("np_, platform, refused", [
    (2, "tpu", True),
    (2, "", True),        # jax would pick: a TPU where there is one
    (2, "tpu,cpu", True),
    (2, "cpu", False),
    (1, "tpu", False),    # one process may drive every local chip
    (1, "", False),
])
def test_shared_accelerator_rule(np_, platform, refused):
    assert (launcher._shared_accelerator_error(np_, platform)
            is not None) == refused


def test_launcher_refuses_np2_on_a_non_cpu_platform():
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "--platform", "tpu", "--", sys.executable, "-c", "print('ran')"],
        cwd=REPO, capture_output=True, text=True, timeout=scaled(60))
    assert res.returncode == 2, res.stdout + res.stderr
    assert "cannot share" in res.stderr and "ran" not in res.stdout


def _import_header(src: str) -> str:
    """The leading import statements of a worker script."""
    out, continued = [], False
    for line in src.strip().splitlines():
        if continued or line.startswith(("import ", "from ")):
            out.append(line)
            continued = line.rstrip().endswith("\\")
        elif line.strip():
            break
    return "\n".join(out)


def test_bench_and_soak_workers_never_import_jax():
    """bench.py spawns these from a parent that may hold the chip; they
    are engine-only by design, and this keeps them so."""
    import bench

    headers = [_import_header(src) for src in (
        bench._FAULT_WORKER, bench._ELASTIC_WORKER, bench._RESTART_WORKER,
        bench.DATAPLANE_WORKER)]
    assert all("horovod_tpu" in h for h in headers), headers
    code = "\n".join(headers + [
        "import horovod_tpu.serving.worker, horovod_tpu.relay",
        "import sys",
        "assert 'jax' not in sys.modules, "
        "sorted(m for m in sys.modules if m.startswith('jax'))"])
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=scaled(60))
    assert res.returncode == 0, res.stderr
    assert bench.CHILD_ENV == {"JAX_PLATFORMS": "cpu"}

"""What the chip bring-up added to the program, checked without a chip:
the compile-cache helper, the no-TPU refusals, the launcher's
one-process-per-chip rule, the jax-free bench workers, and the one
yardstick: bench.py times no device, the documents name files that exist,
and PERF.md stays a file one read holds."""

import ast
import glob
import json
import os
import re
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


def _benchmark_names():
    """(workloads, end-to-end metrics, every metric name a result line may
    carry: a per-layer ``stall_share.lm`` is printed as ``stall_share``)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    e2e = [m["name"] for m in spec["end_to_end"]]
    keys = {n for m in spec["end_to_end"] + spec["per_layer"]
            for n in (m["name"], m["name"].split(".")[0])}
    return [w["name"] for w in spec["workloads"]], e2e, keys


def test_bench_times_no_device(monkeypatch):
    """bench.py is the host's phases and nothing else: ``main`` reaches
    every phase it defines without asking for a TPU, no phase builds a model
    or a serving engine, and every line a phase prints says what platform it
    ran on under no name that is a metric of BENCHMARK.json."""
    import bench

    def refuse(what):
        raise AssertionError(f"bench.py asked for a TPU: {what}")

    monkeypatch.setattr(chip, "require_tpu", refuse)
    phases = sorted(n for n, f in vars(bench).items()
                    if callable(f) and n.endswith("bench"))
    ran = []
    for name in phases:
        monkeypatch.setattr(bench, name, lambda name=name: ran.append(name))
    for name in list(os.environ):
        if name.startswith("BENCH_"):
            monkeypatch.delenv(name)
    for argv in ([], ["--fault"], ["--fault", "--elastic"]):
        monkeypatch.setattr(sys, "argv", ["bench.py"] + argv)
        bench.main()
    assert sorted(ran) == phases and len(phases) == 6, (ran, phases)

    with open(os.path.join(REPO, "bench.py")) as f:
        tree = ast.parse(f.read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names}
    imported |= {n.module for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)}
    assert not [m for m in imported if m.startswith((
        "horovod_tpu.models", "horovod_tpu.serving", "horovod_tpu.utils.chip",
        "flax", "optax"))], imported
    _, _, metric_keys = _benchmark_names()
    lines = [n.args[0] for n in ast.walk(tree)
             if isinstance(n, ast.Call) and ast.unparse(n.func) == "json.dumps"]
    assert len(lines) >= len(phases)
    for line in lines:
        fields = dict(zip((k.value for k in line.keys), line.values))
        assert "platform" in fields, ast.unparse(line)
        assert not (set(fields) | {fields["metric"].value}) & metric_keys, \
            ast.unparse(line)


def test_documents_name_files_that_exist():
    """Every path of this repo that README.md or a docs/*.md names (a ``.py``
    or ``docs/*.md`` under one of the tree's own directories, or a script at
    the root) is there: a file deleted without its mention fails here.  The
    upstream project's files are cited as ``reference <path>``."""
    named = re.compile(
        r"(reference\s+)?(?<![\w/.-])((?:examples|tests|benchmarks|docs|"
        r"horovod_tpu)/(?:[\w.-]+/)*[\w-]+\.(?:py|md)"
        r"|(?:bench|chip_smoke)\.py)\b")
    missing = []
    for doc in ["README.md"] + sorted(glob.glob("docs/*.md", root_dir=REPO)):
        with open(os.path.join(REPO, doc)) as f:
            for ref, path in named.findall(f.read()):
                if not ref and not os.path.exists(os.path.join(REPO, path)):
                    missing.append((doc, path))
    assert not missing, missing


def test_perf_md_keeps_its_own_rule():
    """PERF.md is read whole by every session: under 300 lines, 1200
    characters a line and 100000 bytes, and it names every workload and
    every end-to-end metric of BENCHMARK.json."""
    with open(os.path.join(REPO, "PERF.md"), encoding="utf-8") as f:
        text = f.read()
    lines = text.splitlines()
    assert len(lines) < 300, len(lines)
    assert max(map(len, lines)) <= 1200, max(map(len, lines))
    assert len(text.encode("utf-8")) < 100_000
    workloads, e2e, _ = _benchmark_names()
    assert not [n for n in workloads + e2e if f"`{n}`" not in text]

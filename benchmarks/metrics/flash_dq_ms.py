"""Retired at PR 34: no entry of ``BENCHMARK.json`` names this reader, and no
run loads it.  Since PR 27 the backward is one kernel (``flash_bwd_ms``) and
no kernel is named ``hvd_flash_dq``: the metric read 0.0 in every cell.  The
file stays for tests/test_bench_scopes.py (tier-1, no benchmark file), which
loads it by name on an older program's trace; it goes with those cases."""

from benchmarks import scopes


def read(run):
    return scopes.pass_ms(run, "FLASH_DQ")

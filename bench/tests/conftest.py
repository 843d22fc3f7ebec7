"""The benchmark's own tests, run by hand on the CPU at tiny sizes:

    python -m pytest bench/tests
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import run  # noqa: E402

CELLS = ("tpch-q18-sf10.join-groupby", "tpch-q7-sf10.join", "tpch-q18-sf10.groupby")


def tiny(name: str, divisor: int = 10_000) -> dict:
    """The cell as BENCHMARK.json gives it, every table cut to
    1/`divisor` of its rows (at least 64)."""
    cell = run.load_cell(name)
    for table in cell["config"]["tables"].values():
        table["rows"] = max(table["rows"] // divisor, 64)
    return cell


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    """`run.run` with the chip check, the peaks and the device's memory
    reading replaced, and the compile cache off, so that it runs here."""
    import jax

    from repro import compile_cache

    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peaks", lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(run, "peak_bytes_in_use", lambda devices: 1)
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")

    class Args:
        seed = 2**31 + 12345
        seconds = 0.2
        trace = 0

    def go(cell, **kw):
        args = Args()
        for k, v in kw.items():
            setattr(args, k, v)
        return run.run(args, cell)

    return go

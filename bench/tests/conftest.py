"""Shared helpers for the benchmark's own tests (CPU, tiny sizes)."""

import dataclasses

import pytest

from bench import run as R

SEED = 2**31 + 17          # above 32 signed bits, as the driver's seeds are


def tiny(name: str, *, n_docs: int = 1500, rate: float = 8.0) -> R.Cell:
    """A cell of BENCHMARK.json at a size a CPU test holds."""
    cell = R.load_cell(name)
    cell.config = dict(cell.config, n_docs=n_docs)
    cell.mix = dataclasses.replace(cell.mix, rate_qps=rate)
    return cell


@pytest.fixture
def src_path(monkeypatch):
    monkeypatch.syspath_prepend(str(R.ROOT / "src"))

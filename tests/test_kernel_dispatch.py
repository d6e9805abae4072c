"""KernelDispatch: no fallback after a kernel failure, the call counter,
and the compile-cache helper the launchers share."""

import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core import encoding as E
from repro.kernels import ops


class Boom(RuntimeError):
    pass


def _raising(*_args):
    raise Boom("kernel failed")


def _on_accelerator(monkeypatch, dispatch, fake):
    """auto mode as it runs on a chip: an accelerator backend, no floor,
    and ``fake`` in place of the Pallas kernel."""
    monkeypatch.setattr(ops, "_on_accelerator", lambda: True)
    monkeypatch.setattr(dispatch, "backend", "auto")
    monkeypatch.setattr(dispatch, "min", 0)
    monkeypatch.setattr(dispatch, "_kernel", fake)


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_offsets_kernel_failure_raises(monkeypatch, backend):
    _on_accelerator(monkeypatch, E.OFFSETS_SCAN, _raising)
    monkeypatch.setattr(E.OFFSETS_SCAN, "backend", backend)
    sizes = np.arange(1, 1001, dtype=np.int64)
    out = np.full(len(sizes), -1, np.int64)
    with pytest.raises(Boom):
        E.integrate_sizes(sizes, out=out)
    assert (out == -1).all()  # numpy did not step in


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_shuffle_kernel_failure_raises(monkeypatch, backend):
    _on_accelerator(monkeypatch, E.BYTESHUFFLE, _raising)
    monkeypatch.setattr(E.BYTESHUFFLE, "backend", backend)
    arr = np.arange(4096, dtype=np.float32)
    with pytest.raises(Boom):
        E.precondition_column_pages(arr, "split", 1024)


def test_kernel_failure_is_not_remembered(monkeypatch):
    """A failure does not rule the kernel out: the next call runs it."""
    calls = []

    def flaky(sizes):
        calls.append(len(sizes))
        if len(calls) == 1:
            raise Boom("first call fails")
        return np.cumsum(sizes, dtype=np.int64)

    _on_accelerator(monkeypatch, E.OFFSETS_SCAN, flaky)
    sizes = np.arange(10, dtype=np.int64)
    with pytest.raises(Boom):
        E.integrate_sizes(sizes)
    np.testing.assert_array_equal(E.integrate_sizes(sizes), np.cumsum(sizes))
    assert calls == [10, 10]


def test_counter_counts_kernel_calls(monkeypatch):
    _on_accelerator(monkeypatch, E.OFFSETS_SCAN,
                    lambda s: np.cumsum(s, dtype=np.int64))
    before = E.OFFSETS_SCAN.calls
    sizes = np.arange(100, dtype=np.int64)
    for _ in range(3):
        E.integrate_sizes(sizes)
    assert E.OFFSETS_SCAN.calls == before + 3
    # below the floor, and on the numpy backend, nothing is counted
    monkeypatch.setattr(E.OFFSETS_SCAN, "min", 101)
    E.integrate_sizes(sizes)
    monkeypatch.setattr(E.OFFSETS_SCAN, "min", 0)
    monkeypatch.setattr(E.OFFSETS_SCAN, "backend", "numpy")
    E.integrate_sizes(sizes)
    assert E.OFFSETS_SCAN.calls == before + 3


def test_counter_counts_shuffle_calls(monkeypatch):
    _on_accelerator(monkeypatch, E.BYTESHUFFLE,
                    lambda src: np.ascontiguousarray(src.transpose(0, 2, 1)))
    before = E.BYTESHUFFLE.calls
    arr = np.arange(4096, dtype=np.int32)
    got = bytes(E.precondition_column_pages(arr, "split", 1024))
    assert E.BYTESHUFFLE.calls == before + 1
    monkeypatch.setattr(E.BYTESHUFFLE, "backend", "numpy")
    assert bytes(E.precondition_column_pages(arr, "split", 1024)) == got
    assert E.BYTESHUFFLE.calls == before + 1


def test_auto_on_cpu_keeps_numpy(monkeypatch):
    monkeypatch.setattr(E.OFFSETS_SCAN, "backend", "auto")
    monkeypatch.setattr(E.OFFSETS_SCAN, "min", 0)
    monkeypatch.setattr(E.OFFSETS_SCAN, "_kernel", _raising)
    before = E.OFFSETS_SCAN.calls
    sizes = np.arange(1000, dtype=np.int64)
    np.testing.assert_array_equal(E.integrate_sizes(sizes), np.cumsum(sizes))
    assert E.OFFSETS_SCAN.calls == before


def test_unknown_backend_is_refused(monkeypatch):
    monkeypatch.setenv("REPRO_TESTK_BACKEND", "gpu")
    with pytest.raises(ValueError):
        ops.KernelDispatch("testk", lambda: None, min_default=0)


# ---------------------------------------------------------------------------
# compile cache


@pytest.fixture
def restore_cache_dir():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert enable_compile_cache() == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    assert not first.startswith(tempfile.gettempdir())
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()

"""Public kernel entry points with backend dispatch.

On TPU the Pallas kernels run compiled; on a CPU backend (tests) they run
through ``interpret=True`` when forced, and the ``ref`` oracles compile
through XLA otherwise.  Model code calls these wrappers only.

``use_pallas``: None = auto (pallas on TPU, ref elsewhere), True = force
pallas (interpret on CPU), False = force ref.  :func:`flash_attention`
refines auto by shape: on a TPU it runs the train kernel (Pallas forward
and backward, so the differentiated train step can take it) where the
call's shapes allow, and the XLA attention everywhere else (see there).

This module also owns :class:`KernelDispatch` — the ONE auto/numpy/pallas
backend selector shared by every host-facing encode/decode kernel
(offsets scan, byteshuffle, the device decode chain).  The module itself
stays import-light: jax and the kernel implementations load lazily inside
the wrappers, so ``from repro.kernels.ops import KernelDispatch`` costs
nothing on the write/read hot paths that only need the dispatch logic.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
from typing import Callable, Optional

# ---------------------------------------------------------------------------
# host-side backend dispatch (shared by core/encoding.py and the reader's
# device decode path)

#: the global default backend for every dispatched kernel; per-kernel
#: ``REPRO_<NAME>_BACKEND`` variables override it (DESIGN.md §7.4)
GLOBAL_BACKEND_ENV = "REPRO_KERNEL_BACKEND"


class KernelDispatch:
    """auto / numpy / pallas backend selection for one kernel family.

    One copy of the selection logic for every host-facing encode/decode
    kernel: environment resolution, the "auto never pays a cold jax
    import on the hot path" rule, the size floor below which the host
    path is kept, and the counter of calls that went to the kernel.

    Resolution order for the backend string:

    1. ``REPRO_<NAME>_BACKEND`` — the per-kernel override;
    2. ``REPRO_KERNEL_BACKEND`` — the global default for all kernels;
    3. ``"auto"``.

    ``auto`` selects the Pallas kernel for every call at or above the
    size floor ``REPRO_<NAME>_PALLAS_MIN`` (units chosen by the call
    site: elements or bytes) when jax is *already imported* by the
    application (never pay a multi-second cold import inside a seal or
    decode path) AND the default backend is an accelerator; ``pallas``
    forces the kernel for every call (interpret mode on CPU — the
    bit-identity test configuration); ``numpy`` pins the host path.

    There is no fallback after a failure: a kernel that raises, raises
    out of the call site.  ``calls`` counts the calls that ran the
    kernel.  The instance is mutable on purpose: tests monkeypatch
    ``backend`` and ``min``.
    """

    BACKENDS = ("auto", "numpy", "pallas")

    def __init__(self, name: str, loader: Callable[[], Callable],
                 min_default: int) -> None:
        self.name = name
        env = f"REPRO_{name.upper()}_BACKEND"
        self.backend = os.environ.get(
            env, os.environ.get(GLOBAL_BACKEND_ENV, "auto")
        ).lower()
        if self.backend not in self.BACKENDS:
            raise ValueError(
                f"{env}/{GLOBAL_BACKEND_ENV}={self.backend!r}: expected one "
                f"of {self.BACKENDS}")
        self.min = int(
            os.environ.get(f"REPRO_{name.upper()}_PALLAS_MIN", str(min_default))
        )
        self.calls = 0
        self._lock = threading.Lock()
        self._loader = loader
        self._kernel: Optional[Callable] = None

    def want(self, measure: int) -> bool:
        """Does a call of size ``measure`` go to the kernel?"""
        if self.backend == "pallas":
            return True
        return (self.backend == "auto" and measure >= self.min
                and _on_accelerator())

    def run(self, *args):
        """Run the kernel (loaded on first use) and count the call."""
        if self._kernel is None:
            self._kernel = self._loader()
        with self._lock:
            self.calls += 1
        return self._kernel(*args)


def _on_accelerator() -> bool:
    """True when jax is already imported AND its default backend is an
    accelerator — the ``auto`` rule every dispatcher shares."""
    if "jax" not in sys.modules:
        return False
    import jax

    return jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# model-kernel entry points (jax imported lazily per call)


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _resolve(use_pallas: Optional[bool]):
    """-> (run_pallas, interpret)"""
    if use_pallas is None:
        return (_on_tpu(), False)
    return (use_pallas, not _on_tpu())


def offsets_scan(lengths, use_pallas: Optional[bool] = None, **kw):
    run, interp = _resolve(use_pallas)
    if run:
        from .offsets_scan import offsets_scan as k

        return k(lengths, interpret=interp, **kw)
    from . import ref

    return ref.offsets_scan_ref(lengths)


def byteshuffle(planes, use_pallas: Optional[bool] = None, **kw):
    run, interp = _resolve(use_pallas)
    if run:
        from .byteshuffle import byteshuffle as k

        return k(planes, interpret=interp, **kw)
    from . import ref

    return ref.byteshuffle_ref(planes)


def unsplit_pages(planes, use_pallas: Optional[bool] = None, **kw):
    """Inverse page-batched byteshuffle: (P, itemsize, per) -> (P, per, itemsize)."""
    run, interp = _resolve(use_pallas)
    if run:
        from .decode_pages import unsplit_pages as k

        return k(planes, interpret=interp, **kw)
    from . import ref

    return ref.unsplit_pages_ref(planes)


def decode_offset_pages(planes, use_pallas: Optional[bool] = None, **kw):
    """Fused offset-column decode: split u64 zigzag deltas -> int32 offsets."""
    run, interp = _resolve(use_pallas)
    if run:
        from .decode_pages import decode_offset_pages as k

        return k(planes, interpret=interp, **kw)
    from . import ref

    return ref.decode_offset_pages_ref(planes)


class PathCounter:
    """Calls of one kernel family lowered on each of its paths.

    ``calls[path]`` counts them, as :attr:`KernelDispatch.calls` counts
    the calls that ran a kernel; each also leaves a ``<name>.<path>``
    record in ``core.stats`` (key: the call's shapes and dtype), kept
    like the ``compile`` records while a profiler session is active.
    A jitted caller lowers once per trace, so the counts are traces."""

    def __init__(self, name: str, paths) -> None:
        self.name = name
        self.calls = dict.fromkeys(paths, 0)
        self._lock = threading.Lock()

    def count(self, path: str, key) -> None:
        if getattr(_uncounted, "on", False):
            return
        with self._lock:
            self.calls[path] += 1
        from ..core import stats

        stats.note(f"{self.name}.{path}", key)


_uncounted = threading.local()


@contextlib.contextmanager
def uncounted():
    """No :class:`PathCounter` counts on this thread inside the block: a
    trace made only to measure (``models/backbone``'s remat probe) lowers
    nothing."""
    prev = getattr(_uncounted, "on", False)
    _uncounted.on = True
    try:
        yield
    finally:
        _uncounted.on = prev


#: the paths of :func:`flash_attention`: the train kernel, the
#: forward-only kernel asked for with ``use_pallas=True``, the XLA attention
ATTENTION = PathCounter("attention", ("kernel", "forward_kernel", "xla"))


#: the activation-checkpoint policy each remat'd layer stack took
#: (``models/backbone._run_layers``): ``dots`` keeps its layers' matmul
#: outputs for the backward, ``full`` keeps nothing inside a layer.  One
#: count per stack lowered; the record's key holds the stack's shapes,
#: the bytes the saving policy keeps and the budget they were held to
REMAT = PathCounter("remat", ("dots", "full"))


def device_memory_limit() -> Optional[int]:
    """The bytes a program may hold on the first local device
    (``memory_stats()["bytes_limit"]``); None where the backend reports
    no limit (the CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def _single_device() -> bool:
    """No sharding rules over more than one device are active: a Pallas
    call is not partitioned across a mesh."""
    from ..distributed.sharding import current_rules

    rules = current_rules()
    return rules is None or rules.mesh.size == 1


def _train_kernel_fits(q, k, v, causal, window) -> bool:
    """The train kernel serves this attention call: a TPU, one device,
    a causal mask without a window, Sq == Sk on a block multiple, a pair
    of q/k and v head dims the kernel supports (MLA's 192/128 among
    them), and one dtype for q, k and v."""
    if not (_on_tpu() and causal and window is None):
        return False
    from .flash_attention import TRAIN_HEAD_DIMS, train_block

    s, d = q.shape[2], q.shape[3]
    return (k.shape[2] == s and train_block(s) is not None
            and k.shape[3] == d and (d, v.shape[3]) in TRAIN_HEAD_DIMS
            and q.dtype == k.dtype == v.dtype and _single_device())


def flash_attention(q, k, v, causal=True, window=None, scale=None,
                    use_pallas: Optional[bool] = None, impl: str = "ref", **kw):
    """Causal (optionally sliding-window) GQA attention.

    The rule, on the input and not on a knob:

    - auto (``None``) on a TPU runs the train kernel
      (``kernels/flash_attention.flash_attention_train``: Pallas forward
      and backward, the scores never in HBM) when
      :func:`_train_kernel_fits`: causal, no window, ``Sq == Sk`` a
      multiple of the kernel's block, a supported pair of q/k and v head
      dims, one dtype, no multi-device mesh.  The train step takes it;
    - every other auto call (prefill or decode with ``Sq != Sk``, head
      dims outside the kernel's pairs, a sliding window, a mesh, any CPU
      backend) and
      ``use_pallas=False`` compile the XLA attention selected by
      ``impl``: "ref" (naive softmax — the paper-faithful baseline shape)
      or "chunked" (online-softmax scan over kv blocks);
    - ``use_pallas=True`` runs the forward-only Pallas kernel
      (``kernels/flash_attention.flash_attention``, no VJP; interpret
      mode off a TPU), as serving asks.

    :data:`ATTENTION` counts each call on its path."""
    key = (tuple(q.shape), tuple(k.shape), tuple(v.shape), str(q.dtype))
    if use_pallas:
        ATTENTION.count("forward_kernel", key)
        _, interp = _resolve(True)
        from .flash_attention import flash_attention as kern

        return kern(q, k, v, causal=causal, window=window,
                    scale=scale, interpret=interp, **kw)
    if use_pallas is None and _train_kernel_fits(q, k, v, causal, window):
        ATTENTION.count("kernel", key)
        from .flash_attention import flash_attention_train

        return flash_attention_train(q, k, v, scale=scale)
    ATTENTION.count("xla", key)
    from . import ref

    if impl == "chunked":
        return ref.flash_attention_chunked(q, k, v, causal=causal,
                                           window=window, scale=scale)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale)


#: the paths of the MoE layer (``models/moe.py``): the dropless layer's
#: grouped matmuls as the Pallas ``gmm`` (megablox, shipped with jax), and
#: the GShard capacity dispatch of a multi-device mesh.  One count per MoE
#: layer lowered
MOE = PathCounter("moe", ("gmm", "capacity"))
#: the dropless layer's row buffer is a multiple of this: the gmm row tile
GROUPED_ROWS = 512
#: gmm tiles (rows, contraction, output columns), the fastest of five
#: measured at the moonlight cell's shapes on a v5e, where gmm ran the
#: held experts' SwiGLU, forward and gradient, in 12.1 ms against
#: ``jax.lax.ragged_dot``'s 16.2 ms (PERF.md)
GMM_TILING = (512, 512, 1024)


def moe_path() -> str:
    """The MoE layer's path: ``capacity`` under a multi-device mesh, else
    the dropless layer's ``gmm``."""
    return "gmm" if _single_device() else "capacity"


def grouped_matmul(lhs, rhs, group_sizes):
    """``(M, K) x (G, K, N) -> (M, N)`` in ``lhs``'s dtype (f32
    accumulation): rows ``sum(group_sizes[:i])`` onward, ``group_sizes[i]``
    of them, times ``rhs[i]``.  Rows past ``sum(group_sizes)`` are left
    undefined (the caller masks them).  The Pallas ``gmm``, a custom VJP
    whose weight gradient is the ``tgmm`` kernel; interpret mode off a
    TPU."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, k = lhs.shape
    n = rhs.shape[2]
    tiling = (min(GMM_TILING[0], m), min(GMM_TILING[1], k),
              min(GMM_TILING[2], n))
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                        interpret=not _on_tpu())


def decode_attention(q, k, v, length=None, window=None, scale=None,
                     use_pallas: Optional[bool] = None, **kw):
    run, interp = _resolve(use_pallas)
    if run:
        from .decode_attention import decode_attention as kern

        return kern(q, k, v, length=length, window=window,
                    scale=scale, interpret=interp, **kw)
    from . import ref

    return ref.decode_attention_ref(q, k, v, length=length, window=window,
                                    scale=scale)


def rwkv6(r, k, v, w, u, use_pallas: Optional[bool] = None, **kw):
    """-> (out (B,H,T,Dv), final_state (B,H,Dk,Dv))."""
    run, interp = _resolve(use_pallas)
    if run:
        from .rwkv6_scan import rwkv6_scan as kern

        return kern(r, k, v, w, u, interpret=interp, **kw)
    from . import ref

    return ref.rwkv6_ref(r, k, v, w, u)


def mamba2(x, log_a, Bm, Cm, use_pallas: Optional[bool] = None, **kw):
    """-> (out (B,H,T,P) without D-skip, final_state (B,H,N,P))."""
    run, interp = _resolve(use_pallas)
    if run:
        from .mamba2_ssd import mamba2_ssd as kern

        return kern(x, log_a, Bm, Cm, interpret=interp, **kw)
    import jax

    from . import ref

    D0 = jax.numpy.zeros((x.shape[1],), x.dtype)
    return ref.mamba2_ref(x, log_a, Bm, Cm, D0)

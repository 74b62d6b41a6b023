"""jit'd dispatch wrappers for the kernel package, and the one place that
decides how Pallas kernels run on this process's platform.

On a TPU the Pallas kernels run compiled and are the default path; elsewhere
the pure-jnp references run unless ``use_pallas(True)`` routes to the
kernels, which then execute in Pallas interpret mode.  Interpret mode is reachable on the CPU only: asking for it
on a TPU raises.  The model/risk stacks only ever import from here.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


@functools.lru_cache(maxsize=None)
def _ssd_pallas_vjp(chunk: int, interpret: bool):
    """Differentiable wrapper: Pallas forward, reference-VJP backward (the
    backward rematerialises through the jnp oracle — correct by construction;
    a dedicated backward kernel is a recorded perf-iteration TODO)."""
    from repro.kernels import ssd_scan

    @jax.custom_vjp
    def f(x, dt, a, B, C, h0):
        return ssd_scan.ssd_chunked_pallas(x, dt, a, B, C, chunk=chunk,
                                           initial_state=h0,
                                           interpret=interpret)

    def fwd(x, dt, a, B, C, h0):
        return f(x, dt, a, B, C, h0), (x, dt, a, B, C, h0)

    def bwd(res, cts):
        x, dt, a, B, C, h0 = res
        _, vjp = jax.vjp(
            lambda *args: _ref.ssd_chunked_ref(*args[:5], chunk,
                                               initial_state=args[5]),
            x, dt, a, B, C, h0)
        return vjp(cts)

    f.defvjp(fwd, bwd)
    return f


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for this platform.  ``None`` picks compiled
    kernels on a TPU and the interpreter elsewhere; ``True`` on a TPU is an
    error, so no TPU code path runs a kernel in the interpreter.
    ``False`` is honoured everywhere (it is how a kernel is compiled for a
    described TPU from a CPU host)."""
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError("Pallas interpret mode is CPU-only; on a TPU the "
                         "kernels run compiled (interpret=None or False)")
    return bool(interpret)


def default_kernel_backend() -> str:
    """Paged-attention backend for serving: the fused Pallas kernels on a
    TPU, the jnp gather reference elsewhere."""
    return "pallas" if on_tpu() else "jnp"


# "pallas"/"interpret" hold an explicit choice, or None for the platform's
_STATE = {"pallas": None, "interpret": None}


def use_pallas(on: Optional[bool], interpret: Optional[bool] = None) -> None:
    """Route ops to the Pallas kernels (``True``), the jnp references
    (``False``) or the platform default (``None``)."""
    _STATE["pallas"] = on
    _STATE["interpret"] = interpret


def pallas_enabled() -> bool:
    return on_tpu() if _STATE["pallas"] is None else _STATE["pallas"]


def aggregate_lookup(rows: int, layers: Optional[int] = None) -> str:
    """The lookup :func:`aggregate_loss` runs for a catalog of ``rows`` ELT
    rows: "jnp" (the reference), or the Pallas kernel's "vmem" (combined
    occurrence losses resident in VMEM, where they fit its budget) or "xla"
    (XLA's gather of them from HBM).  A tower of ``layers`` layers (terms
    with a layer axis) names its layer count too, as in "vmem.layers8"; one
    layer given without the axis (``None``) does not."""
    if not pallas_enabled():
        path = "jnp"
    else:
        from repro.kernels import aggregate_loss as _agg
        path = _agg.lookup_path(rows, layers or 1)
    return path if layers is None else f"{path}.layers{layers}"


# ---------------------------------------------------------------------------
def ssd(x, dt, a_log_decay, B, C, chunk: int,
        initial_state: Optional[jax.Array] = None):
    """Chunked SSD scan; see kernels.ref.ssd_chunked_ref for the contract.

    Pads the sequence up to a chunk multiple (dt=0, a=0 pads are state-neutral:
    decay exp(0)=1 and zero input leave the recurrence unchanged)."""
    L = x.shape[1]
    pad = (-L) % chunk
    if pad:
        padL = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, a_log_decay, B, C = map(padL, (x, dt, a_log_decay, B, C))
        y, h = ssd(x, dt, a_log_decay, B, C, chunk, initial_state)
        return y[:, :L], h
    if pallas_enabled():
        b, _, H, P = x.shape
        N = B.shape[-1]
        h0 = (initial_state if initial_state is not None
              else jnp.zeros((b, H, P, N), jnp.float32))
        fn = _ssd_pallas_vjp(chunk, resolve_interpret(_STATE["interpret"]))
        return fn(x, dt, a_log_decay, B, C, h0)
    return _ref.ssd_chunked_ref(x, dt, a_log_decay, B, C, chunk,
                                initial_state=initial_state)


def ssd_decode_step(state, x_t, dt_t, a_t, B_t, C_t):
    return _ref.ssd_decode_step_ref(state, x_t, dt_t, a_t, B_t, C_t)


def aggregate_loss(event_ids, elt_losses, occ_ret, occ_lim, agg_ret, agg_lim,
                   chunk: int = 128):
    """Year-loss per trial (paper Algorithm 3): ``(T,)`` for one layer's
    terms (``occ_ret``/``occ_lim`` ``(M,)``, scalar aggregate terms),
    ``(T, L)`` for a portfolio of L layers' (``(L, M)`` and ``(L,)``).

    Pads the event axis to a chunk multiple with event id 0 — the pad event
    row of every ELT is zero by contract, so pads contribute no loss.  On
    the Pallas path a catalog whose combined occurrence losses fit the
    kernel's VMEM budget (the paper's 2,000,001 rows do) is looked up in
    VMEM, a larger one by XLA's gather; :func:`aggregate_lookup` says which
    runs."""
    K = event_ids.shape[1]
    chunk = min(chunk, K)
    pad = (-K) % chunk
    if pad:
        event_ids = jnp.pad(event_ids, ((0, 0), (0, pad)))
    if pallas_enabled():
        from repro.kernels import aggregate_loss as _agg
        return _agg.aggregate_loss_pallas(
            event_ids, elt_losses, occ_ret, occ_lim, agg_ret, agg_lim,
            interpret=_STATE["interpret"])
    return _ref.aggregate_loss_chunked_ref(
        event_ids, elt_losses, occ_ret, occ_lim, agg_ret, agg_lim, chunk=chunk)

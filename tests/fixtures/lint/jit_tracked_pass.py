"""MUST-PASS: the blessed dispatch discipline — every fetched-program
call runs under ``dispatch.jit_tracker`` so the compute plane can
attribute cache behaviour and device time. Pins the idioms the serving
paths actually use: the inline with-item tracker (index/device.py), the
tracker-bound-to-a-Name idiom (query/compiler.py keeps the tracker to
read ``tracker.seconds`` after the block), the factory itself (returns
``jax.jit(...)`` — constructing is not dispatching), calls inside the
traced set (tracing is one program, not a dispatch), and a
module-level decorated kernel called by its own host wrapper
(encoding/m3tsz/tpu.py style — the wrapper is the tracked unit one
level up)."""

import functools

import jax
import jax.numpy as jnp

from m3_tpu.utils import dispatch


@functools.lru_cache(maxsize=64)
def _program(sig: tuple):
    """Factory: returning the jit IS the blessed construction site."""

    def run(v):
        return jnp.cumsum(v) * float(len(sig))

    return jax.jit(run)


def eval_inline_tracked(sig, padded):
    prog = _program(sig)
    with dispatch.jit_tracker("fixture_op", prog, sig=str(sig)):
        return prog(padded)      # blessed: inline tracker with-item


def eval_named_tracker(sig, padded):
    prog = _program(sig)
    tracker = dispatch.jit_tracker("fixture_op", prog, sig=str(sig))
    with tracker:                # blessed: tracker bound to a Name
        out = prog(padded)
    return out, tracker.seconds


@jax.jit
def _kernel(v):
    # traced set: this call graph is ONE program under trace — the
    # nested helper call below is not a dispatch
    return _traced_helper(v) + 1.0


def _traced_helper(v):
    return jnp.cumsum(v)


def host_wrapper(values):
    """Module-level decorated kernel called by its own wrapper: the
    wrapper is the tracked unit one level up (out of rule scope)."""
    return _kernel(jnp.asarray(values))

"""Host wrapper for the query-batched fused weightings kernel: pad,
dispatch and the copy back."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.weightings.ref import batched_weightings_ref
from repro.kernels.weightings.weightings import batched_weightings_pallas


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def q_bucket(q: int) -> int:
    """Power-of-two bucketing contract for the query-batch dimension.

    Serving waves produce arbitrary (ragged) group sizes — any mix of plain
    queries and GROUP BY leaf fan-outs — and a jit recompile per distinct Q
    would dwarf the dispatch being amortized. Launch sizes therefore bucket
    UP to the next power of two, with a floor of 8 (below which padding is
    cheaper than another compiled variant): at most ``log2(max_group) - 2``
    compiled variants ever exist per (L, K1, K2) shape. Padded query rows
    are value-safe garbage and are sliced away by the caller.

    The construction-side analogue is ``BuildParams.pair_chunk`` for
    ``kernels.hist2d.batched_hist2d``, which buckets DOWN (see there: the
    chunk bound is a memory ceiling, not a floor).
    """
    return max(8, 1 << (int(q) - 1).bit_length())


_batched_ref_jit = jax.jit(batched_weightings_ref)


def _f32(x, shape=None):
    """``x`` as float32, zero-padded up to ``shape``. Returned as it is
    where it already is both, as ``FastPath._get_stack``'s device-resident
    stacks are: then no op is dispatched for it."""
    shape = tuple(x.shape) if shape is None else shape
    if x.dtype == np.float32 and tuple(x.shape) == shape:
        return x
    x = jnp.asarray(x, jnp.float32)
    if tuple(x.shape) != shape:
        x = jnp.pad(x, [(0, n - m) for n, m in zip(shape, x.shape)])
    return x


def batched_weightings(h_stack, beta, fold, hx, *, use_pallas: bool = True,
                       interpret: bool | None = None) -> np.ndarray:
    """Query-batched fused weightings: beta (Q, L, K2) -> (Q, K1), as a
    host NumPy array.

    See ref.batched_weightings_ref for semantics. Q is bucketed to a power
    of two (``q_bucket``: UP to the next pow-2, min 8) so ragged serving
    group sizes — plain queries and GROUP BY leaf fan-outs alike — reuse a
    bounded set of compiled launch variants; K1/K2 pad to 128-lane
    multiples. Padding is value-safe: padded beta rows produce garbage rows
    that are sliced away; padded K entries are zero.

    One launch is one device round trip: ``beta`` is padded on the host and
    handed to the jitted kernel as a NumPy array, so the dispatch makes the
    only host-to-device copy; the whole padded ``(q_bucket(Q), K1p)`` result
    comes back in one copy and is sliced on the host. The h/fold/hx stacks
    should already be device-resident float32 and 128-padded
    (``FastPath._get_stack``), and then pass through untouched; others are
    converted and padded here, with ops of their own. ``interpret`` None
    asks ``jax.default_backend()``; a caller that launches often decides it
    once and passes it.
    """
    beta = np.asarray(beta, np.float32)
    q, el, k2 = beta.shape
    k1 = fold.shape[1]
    qp = q_bucket(q)

    if not use_pallas:
        bpad = np.zeros((qp, el, k2), np.float32)
        bpad[:q] = beta
        out = _batched_ref_jit(_f32(h_stack), bpad, _f32(fold), _f32(hx))
        return np.asarray(out)[:q]

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k2p = _round_up(k2, 128)
    k1p = _round_up(k1, 128)
    bpad = np.zeros((el, qp, k2p), np.float32)
    bpad[:, :q, :k2] = np.swapaxes(beta, 0, 1)
    out = batched_weightings_pallas(
        _f32(h_stack, (el, k2p, k2p)), bpad, _f32(fold, (el, k1p, k2p)),
        _f32(hx, (el, k2p)), interpret=bool(interpret))
    return np.asarray(out)[:q, :k1]

"""Feature-level early exiting with statistics-based PCA (FEE-sPCA, paper §IV-A).

Functional (jit-able) semantics of the online search step in Fig. 6: distances
are accumulated segment by segment (one segment = one DRAM-burst group on the
NDP, one VMEM feature block on TPU); after segment k the estimated full
distance

    est_k = alpha_k * part_k / beta_k - margin_k

is compared with the beam threshold; the first segment where est_k >= threshold
rejects the candidate and stops its remaining feature traffic.

This module is the pure-jnp oracle shared by the search loop and by
``kernels/ref.py``; the Pallas kernel in ``kernels/fee_distance.py`` implements
the same contract with block-level DMA skipping.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

BIG = jnp.float32(3.0e38)


@dataclasses.dataclass
class FeeParams:
    """Typed FEE-sPCA estimation parameters (one entry per segment).

    Registered as a JAX pytree so it can be closed over, passed through jit /
    vmap / shard_map, and donated like any other array bundle.  Static config
    (seg width, metric) deliberately lives in ``SearchConfig`` / ``IndexSpec``,
    not here — this is pure device data.
    """

    alpha: jnp.ndarray   # (S,) energy ratios, Eq. 3
    beta: jnp.ndarray    # (S,) Chebyshev correction, >= 1 (l2)
    margin: jnp.ndarray  # (S,) additive margin (ip); zeros for l2

    @property
    def n_seg(self) -> int:
        return self.alpha.shape[0]

    @classmethod
    def identity(cls, n_seg: int) -> "FeeParams":
        """alpha=beta=1, margin=0: plain d_part early exit (no estimation)."""
        return cls(alpha=jnp.ones(n_seg, jnp.float32),
                   beta=jnp.ones(n_seg, jnp.float32),
                   margin=jnp.zeros(n_seg, jnp.float32))

    @classmethod
    def coerce(cls, obj) -> "FeeParams | None":
        """Accept FeeParams, a legacy alpha/beta/margin dict, or None."""
        if obj is None or isinstance(obj, cls):
            return obj
        return cls(alpha=jnp.asarray(obj["alpha"]),
                   beta=jnp.asarray(obj["beta"]),
                   margin=jnp.asarray(obj["margin"]))

    def as_dict(self) -> dict:
        return dict(alpha=self.alpha, beta=self.beta, margin=self.margin)

    def split(self, n_coarse: int) -> "tuple[FeeParams, FeeParams]":
        """Per-tier parameter views for tiered storage: checkpoints
        ``[0, n_coarse)`` drive the resident coarse tier's exit decisions,
        the rest correct the residual continuation.  The fit is already
        per-checkpoint (each alpha/beta/margin entry corrects its own
        prefix), so the tier slices *are* the per-tier re-fit, and their
        concatenation reproduces the unsplit sequence exactly — which is
        what keeps tiered scoring bit-identical to packed."""
        return (FeeParams(self.alpha[:n_coarse], self.beta[:n_coarse],
                          self.margin[:n_coarse]),
                FeeParams(self.alpha[n_coarse:], self.beta[n_coarse:],
                          self.margin[n_coarse:]))


jax.tree_util.register_dataclass(
    FeeParams, data_fields=["alpha", "beta", "margin"], meta_fields=[])


@partial(jax.jit, static_argnames=("seg", "metric"))
def fee_distance(q, x, threshold, alpha, beta, margin, *, seg: int, metric: str = "l2"):
    """FEE-sPCA distance of candidates ``x`` (C, D) against query ``q`` (D,).

    Returns (score, rejected, segs_used):
      score     (C,) full score (squared L2 / negated IP) — exact for survivors
      rejected  (C,) bool, True if early exit triggered before the last segment
      segs_used (C,) int32, number of segments actually touched (memory model)
    """
    c, d = x.shape
    s = d // seg
    if metric == "l2":
        per = ((x - q[None, :]) ** 2).reshape(c, s, seg).sum(-1)
    elif metric == "ip":
        per = -(x * q[None, :]).reshape(c, s, seg).sum(-1)
    else:
        raise ValueError(metric)
    cum = jnp.cumsum(per, axis=1)                              # (C, S) partial scores
    est = alpha[None, :] * cum / beta[None, :] - margin[None, :]
    # exits are only meaningful strictly before the final segment: at the final
    # segment the full score is available anyway.
    exit_mask = est[:, : s - 1] >= threshold                   # (C, S-1)
    any_exit = exit_mask.any(axis=1)
    first_exit = jnp.argmax(exit_mask, axis=1)                 # first True (0 if none)
    segs_used = jnp.where(any_exit, first_exit + 1, s).astype(jnp.int32)
    full = cum[:, -1]
    return full, any_exit, segs_used


@partial(jax.jit, static_argnames=("metric",))
def exact_distance(q, x, *, metric: str = "l2"):
    if metric == "l2":
        return ((x - q[None, :]) ** 2).sum(-1)
    # elementwise, not ``x @ q``: a TPU matmul at default precision rounds
    # its inputs to bf16, and these scores must agree with the FEE kernels'
    # f32 elementwise sums
    return -(x * q[None, :]).sum(-1)

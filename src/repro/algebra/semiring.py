"""Semirings underlying FLIP's vertex-centric execution.

Every FLIP layer computes, per relaxation step, a blocked semiring
matrix-vector product

    cand[v] = ⊕_u ( src_vals[u] ⊗ W[u, v] )        (gather/combine)
    new[v]  = carry[v] ⊕ cand[v]                    (merge)

where W is the tiled adjacency with absent edges holding the ⊕-identity
(`zero`), inactive sources also hold `zero`, and `carry` is whatever the
algorithm folds into the merge (current attributes for monotone
algorithms, the un-pushed residual for delta-PageRank). The semiring
contract the kernels rely on:

  * ⊕ is associative and commutative with identity `zero`;
  * ⊗ has identity `one` and `zero` annihilates it: zero ⊗ x = zero,
    so padding blocks / inactive lanes drop out of the reduction.

Idempotent ⊕ (min/max/or) additionally makes the merge monotone, which is
what the asynchronous cycle simulator needs (see `VertexAlgebra.sim_ok`).

Each op comes in a numpy and a jnp flavour: the numpy side feeds the
cycle simulator and the host-side oracles, the jnp side is traced into
the Pallas kernel / jnp fallback / shard_map engine. Instances are
module-level singletons so they hash by identity and are safe static
arguments to `jax.jit`.

Vector-valued vertex state generalizes the step above to `(n, d)`
feature blocks: `cand[v, f] = ⊕_u (src_vals[u, f] ⊗ W[u, v])`, i.e. the
same contraction applied independently per feature lane `f`. Per tile
that is a `(T, T) × (T, d)` contraction, exposed as `contract_jnp`:
for (+, ×) it IS a matmul (`W.T @ sv`, an MXU op on TPU); for every
other ⊕/⊗ pair it is a broadcast-⊗ then ⊕-reduce over the source axis,
swept in static d-slabs so the `(S, D, slab)` intermediate stays small
inside a Pallas kernel body.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True, eq=False)
class Semiring:
    """(⊕, ⊗) pair with identities and the reductions the kernels need.

    `eq=False` keeps the default identity hash/eq, so passing a semiring
    as a `static_argnames` entry to `jax.jit` caches one executable per
    singleton instead of retracing.
    """

    name: str
    zero: float                 # ⊕-identity; absent edge / inactive lane
    one: float                  # ⊗-identity; source bootstrap value
    add_np: Callable            # ⊕ elementwise, numpy (a ufunc: build_blocks
                                #   uses its `.at` for the edge scatter; a
                                #   plain callable falls back to a slow loop)
    mul_np: Callable            # ⊗ elementwise, numpy
    add_jnp: Callable           # ⊕ elementwise, jnp
    mul_jnp: Callable           # ⊗ elementwise, jnp
    add_reduce_jnp: Callable    # ⊕-reduction along an axis, jnp
    segment_reduce_jnp: Callable  # ⊕-reduction by segment id, jnp
    idempotent: bool            # x ⊕ x == x (min/max/or, not +)
    contract_jnp: Callable = None  # (..., S, d) ⊗ (..., S, D) -> (..., D, d)
                                #   tile contraction over the source axis;
                                #   derived from add/mul when not given

    def monotone_under(self, old_vals, new_vals) -> bool:
        """Warm-start soundness hook for streaming graph updates.

        `old_vals`/`new_vals` are the stored ⊗ operands of the touched
        adjacency cells before and after an update batch, with the
        ⊕-identity standing for an absent edge. Returns True iff every
        new value ⊕-dominates its old value (``new ⊕ old == new``) --
        i.e. the batch only inserts edges or moves weights in the
        ⊕-improving direction. Under an idempotent ⊕ the relaxation
        fixpoint is then monotone in the edge values, so a previous
        fixpoint is a sound resume state: re-seeding only the touched
        sources converges to exactly the from-scratch result. Edge
        deletions / ⊕-worsening reweights (``old`` strictly dominating)
        and non-idempotent ⊕ (re-relaxing would double-count, e.g.
        (+,x) delta-PageRank) return False and require a full recompute.
        """
        if not self.idempotent:
            return False
        old = np.asarray(old_vals, dtype=np.float32)
        new = np.asarray(new_vals, dtype=np.float32)
        return bool(np.all(self.add_np(new, old) == new))

    def __post_init__(self):
        if self.contract_jnp is None:
            object.__setattr__(
                self, "contract_jnp",
                _generic_contract(self.add_reduce_jnp, self.mul_jnp))


def _generic_contract(add_reduce, mul, slab: int = 8):
    """Generic (⊕, ⊗) tile contraction, swept in static d-slabs.

    ``sv`` is ``(..., S, d)`` source state, ``w`` is ``(..., S, D)``
    weights; the result is ``(..., D, d)``:
    ``out[.., v, f] = ⊕_u sv[.., u, f] ⊗ w[.., u, v]``. The broadcast
    intermediate is ``(..., S, D, slab)`` -- bounded by the static slab
    width so the Pallas kernel body's VMEM working set stays small even
    at d=128 (see kernels/frontier/frontier.py's budget math).
    """
    def contract(sv, w):
        d = sv.shape[-1]
        outs = [
            add_reduce(mul(sv[..., :, None, k:k + slab],
                           w[..., :, :, None]), axis=-3)
            for k in range(0, d, slab)
        ]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, -1)
    return contract


def _matmul_contract(sv, w):
    """(+, ×) tile contraction as a true matmul: ``w.T @ sv`` contracts
    the source axis on the MXU ((T, T) × (T, d) per tile). HIGHEST
    precision: at the default one the TPU multiplies f32 operands in a
    single bfloat16 pass, which is off by ~1e-3 relative."""
    return jnp.matmul(jnp.swapaxes(w, -1, -2), sv,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def _segment_or(x, seg, num_segments):
    return jax.ops.segment_max(x, seg, num_segments=num_segments)


MIN_PLUS = Semiring(
    name="min_plus", zero=float("inf"), one=0.0,
    add_np=np.minimum, mul_np=np.add,
    add_jnp=jnp.minimum, mul_jnp=jnp.add,
    add_reduce_jnp=jnp.min,
    segment_reduce_jnp=lambda x, s, n: jax.ops.segment_min(
        x, s, num_segments=n),
    idempotent=True,
)

MAX_MIN = Semiring(
    name="max_min", zero=float("-inf"), one=float("inf"),
    add_np=np.maximum, mul_np=np.minimum,
    add_jnp=jnp.maximum, mul_jnp=jnp.minimum,
    add_reduce_jnp=jnp.max,
    segment_reduce_jnp=lambda x, s, n: jax.ops.segment_max(
        x, s, num_segments=n),
    idempotent=True,
)

# boolean (or, and) carried in {0.0, 1.0} float32 so every layer keeps a
# single dtype; max == or and min == and on that domain.
OR_AND = Semiring(
    name="or_and", zero=0.0, one=1.0,
    add_np=np.maximum, mul_np=np.minimum,
    add_jnp=jnp.maximum, mul_jnp=jnp.minimum,
    add_reduce_jnp=jnp.max,
    segment_reduce_jnp=_segment_or,
    idempotent=True,
)

PLUS_TIMES = Semiring(
    name="plus_times", zero=0.0, one=1.0,
    add_np=np.add, mul_np=np.multiply,
    add_jnp=jnp.add, mul_jnp=jnp.multiply,
    add_reduce_jnp=jnp.sum,
    segment_reduce_jnp=lambda x, s, n: jax.ops.segment_sum(
        x, s, num_segments=n),
    idempotent=False,
    contract_jnp=_matmul_contract,
)

SEMIRINGS = {s.name: s for s in (MIN_PLUS, MAX_MIN, OR_AND, PLUS_TIMES)}

"""Host-side decision tables derived from the branchless policy ops.

The serving pool's control plane runs on the host (numpy) but must make
the *same* ②③④ decisions as the jitted simulator. Since each of those
decisions, for a control plane without PC tables or PCAL tokens, is a pure
function of the warp/sequence type, we evaluate the ops once over all
``NUM_TYPES`` types and cache the result as numpy lookup tables — the ops
remain the single source of truth for mechanism semantics.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import warp_types as WT
from repro.policy import ops
from repro.policy.spec import PolicyArrays

I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class DecisionTables:
    """Per-warp-type decisions for one policy, as numpy arrays."""
    bypass_by_type: np.ndarray   # bool[NUM_TYPES]  ②
    rank_by_type: np.ndarray     # i64[NUM_TYPES]   ③
    hp_by_type: np.ndarray       # bool[NUM_TYPES]  ④

    @staticmethod
    def from_arrays(pa: PolicyArrays, rrip_max: int) -> "DecisionTables":
        types = jnp.arange(WT.NUM_TYPES, dtype=I32)
        # signals a host control plane does not have are neutralized:
        # no probe, token held (PCAL never bypasses), empty PC table,
        # a draw of RAND_RANGE (rand never fires: the cut is at most
        # RAND_RANGE).
        byp = ops.bypass_decision(
            pa, wtype=types,
            probe=jnp.zeros((WT.NUM_TYPES,), bool),
            token_bit=jnp.ones((WT.NUM_TYPES,), bool),
            pc_hits=jnp.zeros((WT.NUM_TYPES,), I32),
            pc_acc=jnp.zeros((WT.NUM_TYPES,), I32),
            pc_req=jnp.zeros((WT.NUM_TYPES,), I32),
            rand_h=jnp.full((WT.NUM_TYPES,), ops.RAND_RANGE, I32))
        rank = ops.insertion_rank(
            pa, wtype=types, eaf_bit=jnp.zeros((WT.NUM_TYPES,), bool),
            rrip_max=rrip_max)
        hp = ops.is_high_priority(pa, types)
        return DecisionTables(
            bypass_by_type=np.asarray(byp, bool),
            rank_by_type=np.asarray(rank, np.int64),
            hp_by_type=np.asarray(hp, bool))

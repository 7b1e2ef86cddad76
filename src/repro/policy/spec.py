"""Policy description: declarative preset + array-backed pytree form.

``Policy`` keeps the human-readable preset (strings name the mechanism at
each decision point). ``PolicyArrays`` is the form the compute paths use:
one-hot select weights over the mechanism menus plus scalar knobs. It is a
NamedTuple of jnp scalars/vectors, i.e. a pytree — it can be passed as a
*traced* jit argument (one trace for all policies) and stacked along a
leading axis for a vmapped policy sweep.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.warp_types import MAX_SAMPLES

F32 = jnp.float32

# mechanism menus — index order is the select-weight order everywhere
BYPASS_MECHS = ("none", "medic", "pcal", "pcbyp", "rand")   # ②
INSERT_MECHS = ("lru", "medic", "eaf")                      # ③
SCHED_MECHS = ("frfcfs", "medic")                           # ④
LABEL_MECHS = ("online", "stale", "oracle")                 # ① labeling


@dataclasses.dataclass(frozen=True)
class Policy:
    """Which mechanism drives each decision point (declarative preset)."""
    name: str
    bypass: str = "none"       # none | medic | pcal | pcbyp | rand
    insertion: str = "lru"     # lru | medic | eaf
    scheduler: str = "frfcfs"  # frfcfs | medic
    rand_p: float = 0.5        # rand bypass probability
    pcal_frac: float = 0.375   # fraction of warps holding tokens
    # ① how warp-type labels track drift (ISSUE 5):
    #   online — periodic reclassification every sampling window (paper);
    #   stale  — classify each warp once, then freeze (phase-0 labels);
    #   oracle — ground-truth per-phase labels from the trace generator.
    labeling: str = "online"
    # sampling window in accesses; 0 = the SimParams default. A
    # policy-visible knob so one vmapped sweep can compare windows. At
    # most ``warp_types.MAX_SAMPLES``, the largest window the integer
    # label ladder decides exactly.
    reclass_interval: int = 0
    # probe cadence in accesses: every ``probe_interval``-th access of a
    # bypassing warp still takes the cache path so the classifier keeps
    # an undiluted cache-path sample (the probe stream) to re-learn
    # from. 0 = the SimParams default (8). Traced and sweepable
    # alongside ``reclass_interval``.
    probe_interval: int = 0

    def __post_init__(self):
        if self.bypass not in BYPASS_MECHS:
            raise ValueError(f"unknown bypass mechanism {self.bypass!r}")
        if self.insertion not in INSERT_MECHS:
            raise ValueError(f"unknown insertion mechanism {self.insertion!r}")
        if self.scheduler not in SCHED_MECHS:
            raise ValueError(f"unknown scheduler {self.scheduler!r}")
        if self.labeling not in LABEL_MECHS:
            raise ValueError(f"unknown labeling mechanism {self.labeling!r}")
        if not 0 <= self.reclass_interval <= MAX_SAMPLES or \
                self.reclass_interval != int(self.reclass_interval):
            raise ValueError(
                f"reclass_interval must be an int in [0, {MAX_SAMPLES}], "
                f"got {self.reclass_interval!r}")
        if self.probe_interval < 0 or \
                self.probe_interval != int(self.probe_interval):
            raise ValueError(
                f"probe_interval must be a non-negative int, got "
                f"{self.probe_interval!r}")


class PolicyArrays(NamedTuple):
    """A ``Policy`` as arrays. All leaves are jnp; a leading batch axis
    (added by ``stack_policies``) makes this a stacked policy batch."""
    bypass_sel: jnp.ndarray    # f32[5] one-hot over BYPASS_MECHS
    ins_sel: jnp.ndarray       # f32[3] one-hot over INSERT_MECHS
    sched_medic: jnp.ndarray   # f32[]  1.0 iff scheduler == "medic"
    rand_p: jnp.ndarray        # f32[]
    pcal_frac: jnp.ndarray     # f32[]
    label_sel: jnp.ndarray     # f32[3] one-hot over LABEL_MECHS
    reclass_interval: jnp.ndarray  # f32[] 0 = SimParams default
    probe_interval: jnp.ndarray    # f32[] 0 = SimParams default


def _one_hot(index: int, n: int) -> jnp.ndarray:
    return jnp.zeros((n,), F32).at[index].set(1.0)


def to_arrays(pol: Policy) -> PolicyArrays:
    return PolicyArrays(
        bypass_sel=_one_hot(BYPASS_MECHS.index(pol.bypass),
                            len(BYPASS_MECHS)),
        ins_sel=_one_hot(INSERT_MECHS.index(pol.insertion),
                         len(INSERT_MECHS)),
        sched_medic=jnp.asarray(1.0 if pol.scheduler == "medic" else 0.0,
                                F32),
        rand_p=jnp.asarray(pol.rand_p, F32),
        pcal_frac=jnp.asarray(pol.pcal_frac, F32),
        label_sel=_one_hot(LABEL_MECHS.index(pol.labeling),
                           len(LABEL_MECHS)),
        reclass_interval=jnp.asarray(pol.reclass_interval, F32),
        probe_interval=jnp.asarray(pol.probe_interval, F32),
    )


def stack_policies(policies: Sequence[Policy]) -> PolicyArrays:
    """Stack presets into one batched ``PolicyArrays`` (leading axis P)."""
    if not policies:
        raise ValueError("stack_policies needs at least one policy")
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[to_arrays(p) for p in policies])

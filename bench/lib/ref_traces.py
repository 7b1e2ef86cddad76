"""Plain reference of the simulator's synthetic trace generator.

Written from the generator's published semantics (counter RNG over
splitmix64, warp archetypes drawn from a mixture, a private working set
per warp, a shared pool and a streaming region), independent of the
program's own code. It covers the specs the benchmark's configurations
state: a static mix, optionally with the legacy mid-kernel archetype
flip (``phase_shift``). Phase schedules are not covered.

Every draw is ``mix64(stream_key + index * GAMMA)``, so one cell's value
depends only on its coordinates, never on evaluation order.
"""
from __future__ import annotations

import zlib
from typing import Dict

import numpy as np

GAMMA = 0x9E3779B97F4A7C15
MASK64 = (1 << 64) - 1
_U = np.uint64

TAG_ARCH, TAG_PHASE, TAG_PHASE_PICK, TAG_WS, TAG_PC, TAG_POOL = 1, 2, 3, 4, 5, 6
TAG_REUSE_U, TAG_SHARED_U, TAG_SHARED_IDX, TAG_WS_IDX = 7, 8, 9, 10

WS_REGION_BITS = 13
POOL_REGION = 1 << WS_REGION_BITS

# warp-type codes, larger = more cache utility
ALL_MISS, MOSTLY_MISS, BALANCED, MOSTLY_HIT, ALL_HIT = range(5)


def mix64_int(z: int) -> int:
    z &= MASK64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
    return z ^ (z >> 31)


def mix64(z):
    with np.errstate(over="ignore"):
        z = np.asarray(z, _U)
        z = (z ^ (z >> _U(30))) * _U(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
        return z ^ (z >> _U(31))


def draw(key: int, idx):
    """64 random bits at ``idx`` of the stream ``key``."""
    with np.errstate(over="ignore"):
        return mix64(_U(key) + np.asarray(idx, _U) * _U(GAMMA))


def uniform(key: int, idx):
    return (draw(key, idx) >> _U(11)).astype(np.float64) * 2.0 ** -53


def randint(key: int, idx, n):
    return (draw(key, idx) % np.asarray(n, _U)).astype(np.int64)


def stream(root: int, tag: int) -> int:
    return mix64_int(root + tag * GAMMA)


def perm12(j, key):
    """Keyed bijection on [0, 4096): three Feistel rounds on 6|6 bits."""
    with np.errstate(over="ignore"):
        j = np.asarray(j, _U)
        key = np.asarray(key, _U)
        left, right = j >> _U(6), j & _U(63)
        for rnd in range(3):
            f = mix64(key + (right | _U(rnd << 6)) * _U(GAMMA)) & _U(63)
            left, right = right, left ^ f
        return ((left << _U(6)) | right).astype(np.int64)


def npow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def type_of_ratio(r, hit_thr: float = 0.8, miss_thr: float = 0.2):
    """Hit ratio -> warp type, compared in float32."""
    r = np.asarray(r, np.float32)
    t = np.full(r.shape, BALANCED, np.int32)
    t = np.where(r <= np.float32(miss_thr), MOSTLY_MISS, t)
    t = np.where(r <= np.float32(1e-6), ALL_MISS, t)
    t = np.where(r >= np.float32(hit_thr), MOSTLY_HIT, t)
    t = np.where(r >= np.float32(1.0 - 1e-6), ALL_HIT, t)
    return t.astype(np.int32)


def compute_gap(intensity: float) -> np.float32:
    return np.float32(4.0 + (1.0 - intensity) * 120.0)


def generate(spec: dict, seed: int, instr_block: int = 8
             ) -> Dict[str, np.ndarray]:
    """One trace of ``spec`` (a dict of the configuration's trace keys:
    name, mix, intensity, n_warps, n_instr, lines_per_instr, n_pcs,
    phase_shift, phase_flip_prob, shared_pool_lines, shared_boost,
    archetypes) at ``seed``.

    Returns lines i32[I, W, L], pcs i32[I, W], oracle_wtype i32[I, W]
    and compute_gap f32."""
    if spec.get("phases"):
        raise NotImplementedError("phase schedules are not covered")
    n_i, n_w, n_l = spec["n_instr"], spec["n_warps"], spec["lines_per_instr"]
    n_pcs = spec["n_pcs"]
    tab = np.asarray(spec["archetypes"], np.float64)
    tab[:, 2] = np.clip(tab[:, 2] * spec["shared_boost"], 0.0, 1.0)
    n_arch = tab.shape[0]
    max_ws = max(int(tab[:, 0].max()), 1)

    fresh_base = max(1 << 22, npow2((n_w + 1) << WS_REGION_BITS))
    fresh_stride = max(1 << 15, npow2(n_i * n_l))
    if fresh_base + n_w * fresh_stride > (1 << 31) - 1:
        raise ValueError("address space overflows int32")

    root = mix64_int(int(seed) + (zlib.crc32(spec["name"].encode()) << 32))
    w = np.arange(n_w, dtype=np.int64)

    cum = np.cumsum(np.asarray(spec["mix"], np.float64))
    arch0 = np.minimum(np.searchsorted(cum, uniform(stream(root, TAG_ARCH), w),
                                       side="right"), n_arch - 1)
    flip_p = spec["phase_flip_prob"] if spec["phase_shift"] else 0.0
    flip = uniform(stream(root, TAG_PHASE), w) < flip_p
    pick = randint(stream(root, TAG_PHASE_PICK), w, n_arch)
    arch = np.stack([arch0, np.where(flip, pick, arch0)], axis=1)  # [W, 2]
    wkey = draw(stream(root, TAG_WS), w)                           # [W]

    ws_size = tab[arch, 0].astype(np.int64)                        # [W, 2]
    reuse = tab[arch, 1]
    shared = tab[arch, 2]
    ws_table = (((w + 1) << WS_REGION_BITS)[:, None]
                + perm12(np.arange(max_ws)[None, :], wkey[:, None]))
    pc_table = randint(stream(root, TAG_PC),
                       w[:, None] * n_pcs + np.arange(n_pcs)[None, :],
                       1 << 16).astype(np.int32)                   # [W, pcs]
    pool = randint(stream(root, TAG_POOL),
                   np.arange(spec["shared_pool_lines"]), POOL_REGION)

    half = n_i // 2
    k_reuse = stream(root, TAG_REUSE_U)
    k_shu = stream(root, TAG_SHARED_U)
    k_shi = stream(root, TAG_SHARED_IDX)
    k_wsi = stream(root, TAG_WS_IDX)
    lines = np.empty((n_i, n_w, n_l), np.int32)
    li = np.arange(n_l, dtype=np.int64)[None, None, :]
    wi = w[None, :, None]
    for i0 in range(0, n_i, instr_block):
        ii = np.arange(i0, min(i0 + instr_block, n_i), dtype=np.int64)
        ph = (ii >= half).astype(np.int64)[:, None]                # [b, 1]
        wsz = ws_size[w[None, :], ph][..., None]                   # [b, W, 1]
        reu = reuse[w[None, :], ph][..., None]
        shr = shared[w[None, :], ph][..., None]
        flat = (ii[:, None, None] * n_w + wi) * n_l + li           # [b, W, L]
        hit = (wsz > 0) & (uniform(k_reuse, flat) < reu)
        use_pool = hit & (shr > 0) & (uniform(k_shu, flat) < shr)
        pool_line = pool[randint(k_shi, flat, spec["shared_pool_lines"])]
        ws_line = ws_table[wi, randint(k_wsi, flat, np.maximum(wsz, 1))]
        fresh = (fresh_base + wi * fresh_stride
                 + ii[:, None, None] * n_l + li)
        lines[ii] = np.where(use_pool, pool_line,
                             np.where(hit, ws_line, fresh))

    ph_all = (np.arange(n_i) >= half).astype(np.int64)
    pcs = pc_table[w[None, :], (np.arange(n_i) % n_pcs)[:, None]]  # [I, W]
    label = np.where(ws_size == 0, ALL_MISS, type_of_ratio(reuse))  # [W, 2]
    oracle = label[w[None, :], ph_all[:, None]].astype(np.int32)    # [I, W]
    return {"lines": lines, "pcs": pcs.astype(np.int32),
            "oracle_wtype": oracle,
            "compute_gap": compute_gap(spec["intensity"])}

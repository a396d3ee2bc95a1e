"""Spyglass predicate kernels: batched device predicate evaluation.

The `Search*`/`Order*`/`Range` routes are selection problems — the 0/1-row
cousin of Prism's selector-matrix `GroupBySum` (PC-MM, arxiv 2504.14497):
given every stored record's column ciphertext, produce a selection mask
(or a sort permutation) in ONE device dispatch instead of a host Python
loop over N records. GME (arxiv 2309.11001) makes the complementary
point: the win comes from comparing against material that is already
device-resident, not re-moved per query — the SearchPlane
(dds_tpu/search) keeps the packed columns pinned and calls down here.

Operand encodings (device side is x64-OFF JAX, so nothing is wider than
uint32):

- OPE ciphertexts (models/ope: `enc(x) = (x + 2^31) * 2^20 + prf`, ≤ 52
  bits, strictly order-preserving) split into two 26-bit lanes
  ``hi = c >> 26, lo = c & (2^26 - 1)``; lexicographic (hi, lo) compare
  IS integer compare, and a two-key `jax.lax.sort` over the lanes IS
  integer ordering. Descending order reuses the same stable sort over the
  complemented lanes (an order-reversing bijection on 26-bit values), so
  ties keep the ascending row order exactly like Python's stable
  `sorted(..., reverse=True)`.
- DET/CHE and LSE-tag equality operands are blake2b-64 digests of the
  ciphertext STRING, split into two uint32 lanes. Digest equality is a
  candidate filter only — 64-bit collisions are possible, so callers must
  confirm candidates against the exact strings host-side (the SearchPlane
  does, via hmac.compare_digest) to keep results bit-for-bit equal to the
  legacy scan.

Dispatch discipline matches ops/foldmany: jitted callables live in
`ops/kernel.fn_cache` keyed by op family (shapes retrace under a single
entry), lookups accounted as compile-cache events of "predicate", every
dispatch timed through `kprof.profiled("predicate", ...)` so
`kernel.predicate.*` spans and histograms line up with the fold kernels'.
"""

from __future__ import annotations

import hashlib

import numpy as np

from dds_tpu.obs import kprof
from dds_tpu.ops.kernel import fn_cache

# 52-bit OPE ciphertexts split into two 26-bit lanes (see module docstring)
LANE_BITS = 26
LANE_MASK = (1 << LANE_BITS) - 1
# largest integer the two-lane packing can represent; values outside
# [0, PACK_MAX] (foreign plaintext ints, negative thresholds) make the
# caller fall back to its host evaluation path
PACK_MAX = (1 << (2 * LANE_BITS)) - 1


def packable(v: int) -> bool:
    return 0 <= v <= PACK_MAX


def pack_ints(values) -> tuple[np.ndarray, np.ndarray]:
    """(hi, lo) uint32 lane arrays for a column of packable ints."""
    n = len(values)
    hi = np.fromiter((v >> LANE_BITS for v in values), np.uint32, n)
    lo = np.fromiter((v & LANE_MASK for v in values), np.uint32, n)
    return hi, lo


def digest_lanes(s: str) -> tuple[int, int]:
    """blake2b-64 of a ciphertext string as two uint32 lanes."""
    d = hashlib.blake2b(s.encode(), digest_size=8).digest()
    return int.from_bytes(d[:4], "big"), int.from_bytes(d[4:], "big")


def pack_digests(values) -> tuple[np.ndarray, np.ndarray]:
    """(hi, lo) uint32 digest-lane arrays for a column of strings."""
    n = len(values)
    pairs = [digest_lanes(s) for s in values]
    hi = np.fromiter((p[0] for p in pairs), np.uint32, n)
    lo = np.fromiter((p[1] for p in pairs), np.uint32, n)
    return hi, lo


def _lex_gt(hi, lo, thi, tlo):
    return (hi > thi) | ((hi == thi) & (lo > tlo))


def _lex_ge(hi, lo, thi, tlo):
    return (hi > thi) | ((hi == thi) & (lo >= tlo))


def compare_mask(hi: np.ndarray, lo: np.ndarray, op: str,
                 threshold: int) -> np.ndarray:
    """Boolean mask of rows whose packed value satisfies `op threshold`.

    op in {"gt", "ge", "lt", "le"}; threshold must be packable (the
    caller clamps or falls back otherwise).
    """
    import jax
    import jax.numpy as jnp

    def run(hi, lo, thi, tlo):
        ge = _lex_ge(hi, lo, thi, tlo)
        gt = _lex_gt(hi, lo, thi, tlo)
        return {"gt": gt, "ge": ge, "lt": ~ge, "le": ~gt}[op]

    fn = fn_cache("predicate", ("cmp", op), lambda: jax.jit(run))
    thi = np.uint32(threshold >> LANE_BITS)
    tlo = np.uint32(threshold & LANE_MASK)
    out = kprof.profiled(
        "predicate",
        lambda: fn(jnp.asarray(hi), jnp.asarray(lo), thi, tlo),
        op=op, n=int(hi.shape[0]),
    )
    return np.asarray(out)


def range_mask(hi: np.ndarray, lo: np.ndarray, lo_bound: int,
               hi_bound: int) -> np.ndarray:
    """Boolean mask of rows with lo_bound <= value <= hi_bound (both
    bounds packable)."""
    import jax
    import jax.numpy as jnp

    def run(hi, lo, ahi, alo, bhi, blo):
        return _lex_ge(hi, lo, ahi, alo) & ~_lex_gt(hi, lo, bhi, blo)

    fn = fn_cache("predicate", ("cmp", "range"), lambda: jax.jit(run))
    out = kprof.profiled(
        "predicate",
        lambda: fn(
            jnp.asarray(hi), jnp.asarray(lo),
            np.uint32(lo_bound >> LANE_BITS), np.uint32(lo_bound & LANE_MASK),
            np.uint32(hi_bound >> LANE_BITS), np.uint32(hi_bound & LANE_MASK),
        ),
        op="range", n=int(hi.shape[0]),
    )
    return np.asarray(out)


def eq_mask(dhi: np.ndarray, dlo: np.ndarray, query: str) -> np.ndarray:
    """Candidate mask of rows whose digest lanes equal the query's.
    Collisions are possible — confirm candidates host-side."""
    import jax
    import jax.numpy as jnp

    fn = fn_cache(
        "predicate", ("digest", "eq"),
        lambda: jax.jit(
            lambda dhi, dlo, qhi, qlo: (dhi == qhi) & (dlo == qlo)
        ),
    )
    qhi, qlo = digest_lanes(query)
    out = kprof.profiled(
        "predicate",
        lambda: fn(jnp.asarray(dhi), jnp.asarray(dlo),
                   np.uint32(qhi), np.uint32(qlo)),
        op="eq", n=int(dhi.shape[0]),
    )
    return np.asarray(out)


def entry_mask(dhi: np.ndarray, dlo: np.ndarray, valid: np.ndarray,
               queries: list[str], mode: str) -> np.ndarray:
    """Candidate mask over an (N, C) element-digest matrix.

    mode "any": rows where ANY valid element matches ANY query
    (SearchEntry with one query, SearchEntryOR with three).
    mode "all": rows where EVERY query matches some valid element
    (SearchEntryAND). Candidates only — confirm host-side.
    """
    import jax
    import jax.numpy as jnp

    def run(dhi, dlo, valid, qhi, qlo):
        # (N, C, Q) element-vs-query digest equality, masked to real
        # (non-padding) elements
        m = (
            (dhi[:, :, None] == qhi[None, None, :])
            & (dlo[:, :, None] == qlo[None, None, :])
            & valid[:, :, None]
        )
        per_query = m.any(axis=1)  # (N, Q): query matched in row
        if mode == "all":
            return per_query.all(axis=1)
        return per_query.any(axis=1)

    fn = fn_cache("predicate", ("entry", mode), lambda: jax.jit(run))
    pairs = [digest_lanes(q) for q in queries]
    qhi = np.asarray([p[0] for p in pairs], np.uint32)
    qlo = np.asarray([p[1] for p in pairs], np.uint32)
    out = kprof.profiled(
        "predicate",
        lambda: fn(jnp.asarray(dhi), jnp.asarray(dlo), jnp.asarray(valid),
                   jnp.asarray(qhi), jnp.asarray(qlo)),
        op=f"entry_{mode}", n=int(dhi.shape[0]),
    )
    return np.asarray(out)


def sort_perm(hi: np.ndarray, lo: np.ndarray, descending: bool) -> np.ndarray:
    """Stable sort permutation over the packed column: row indices in
    ascending (or descending) value order, ties keeping row order — the
    device twin of Python's stable `sorted` by value."""
    import jax
    import jax.numpy as jnp

    def run(hi, lo):
        if descending:
            # complementing both 26-bit lanes reverses the
            # lexicographic order while the stable sort keeps ties in
            # ascending row order — exactly sorted(reverse=True)
            hi = LANE_MASK - hi
            lo = LANE_MASK - lo
        idx = jnp.arange(hi.shape[0], dtype=jnp.int32)
        _, _, perm = jax.lax.sort((hi, lo, idx), num_keys=2,
                                  is_stable=True)
        return perm

    fn = fn_cache("predicate", ("sort", descending), lambda: jax.jit(run))
    out = kprof.profiled(
        "predicate",
        lambda: fn(jnp.asarray(hi), jnp.asarray(lo)),
        op="sort_desc" if descending else "sort_asc", n=int(hi.shape[0]),
    )
    return np.asarray(out)

"""What a fold has to do, from its shapes: bytes moved and int8 operations.

The fold of K ciphertexts of L 16-bit limbs (kept as u32, so 4 L bytes a
row) is a binary tree of K - 1 Montgomery multiplies and one more that
takes the result out of the Montgomery domain. Counted here is what the
algorithm needs on the units whose peaks are published:

- bytes: the K rows read once, and each tree level's products written and
  read again by the next level (K/2 + K/4 + ... rows, about K in all);
- int8 operations: the two Toeplitz (band) matrix products of the
  Montgomery reduction in `mont_mxu._redc`, over base-2^8 digits
  (L8 = 2 L): (L8 x L8) and (2 L8 x L8), each counted once and dense.

Not counted, because no peak is published for the unit that does it: the
L^2 u32 multiply-adds of the schoolbook product and the carry passes, all
on the VPU. So the least time here is a floor under the true floor, and a
share of it reads low; it can never read high.
"""

from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks on file for device kind "
                       f"{device_kind!r}")
    return table[device_kind]


def fold_multiplies(k: int) -> int:
    return k   # k - 1 in the tree, one to leave the Montgomery domain


def fold_bytes(k: int, limbs: int) -> int:
    row = 4 * limbs
    level_rows = 0
    w = 1 << max(1, (k - 1).bit_length())
    while w > 1:
        w //= 2
        level_rows += w
    return k * row + 2 * level_rows * row


def fold_int8_ops(k: int, limbs: int) -> int:
    l8 = 2 * limbs
    macs_per_multiply = l8 * l8 + 2 * l8 * l8
    return 2 * macs_per_multiply * fold_multiplies(k)


def fold_least_seconds(k: int, limbs: int, device_kind: str) -> dict:
    """The least time the chip could take by its published peaks, and
    which of the two bounds it."""
    p = peaks(device_kind)
    by_ops = fold_int8_ops(k, limbs) / p["int8_ops_per_s"]
    by_bytes = fold_bytes(k, limbs) / p["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "int8 operations" if by_ops >= by_bytes else "bytes",
            "by_ops_s": by_ops, "by_bytes_s": by_bytes}

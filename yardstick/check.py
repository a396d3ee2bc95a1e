"""The comparison that decides `correct`.

Every answer is held to the plain reference exactly; there is no
tolerance anywhere in this file.

A column that the mix updates carries a *version scheme*: row i starts at
plaintext v_i and its j-th update writes a plaintext from which j can be
read back, so that the decrypted aggregate names how many updates it
saw. For the additive (Paillier) column the j-th update of row i holds
v_i + j B with B above the sum of every v_i, so an aggregate decrypts to
S0 + c B where c counts the updates folded in. For the multiplicative
(RSA) column it holds v_i g^j mod n and the aggregate to P0 g^c mod n.

Inside a window with writers an aggregate is not an atomic snapshot, but
every key it reads is read linearizably, and the harness never has two
updates of one key in flight. So c is bounded: at least the updates
acknowledged before the aggregate was sent, at most those sent before it
was answered. A stale read breaks the lower bound, a torn or mis-folded
answer decrypts to no S0 + c B at all.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Additive:
    """Paillier column: total(c) = S0 + c B, all below n."""

    base: int    # S0, the sum of the initial plaintexts
    step: int    # B

    def count(self, plain: int, upto: int):
        """The update count an aggregate's plaintext names, or None."""
        c, rest = divmod(plain - self.base, self.step)
        return c if rest == 0 and c >= 0 else None


@dataclass(frozen=True)
class Multiplicative:
    """RSA column: total(c) = P0 g^c mod n."""

    base: int    # P0, the product of the initial plaintexts mod n
    step: int    # g
    modulus: int

    def count(self, plain: int, upto: int):
        want = self.base % self.modulus
        for c in range(upto + 1):
            if want == plain:
                return c
            want = want * self.step % self.modulus
        return None


def judge_aggregate(scheme, plain: int, acked_at_send: int,
                    sent_at_answer: int, sent_in_all: int) -> str | None:
    """None when the decrypted aggregate is one a linearizable store could
    have given in that interval, else why not. `sent_in_all` is every
    update the run ever sent, the most any answer could name."""
    c = scheme.count(plain, sent_in_all)
    if c is None:
        return "torn: decrypts to no total of whole updates"
    if c < acked_at_send:
        return (f"stale: saw {c} updates, {acked_at_send} were "
                "acknowledged before it was sent")
    if c > sent_at_answer:
        return (f"from the future: saw {c} updates, only {sent_at_answer} "
                "had been sent when it was answered")
    return None


def judge_row(got, base: list, columns: dict) -> str | None:
    """A GetSet answer must be, bit for bit, the row as loaded, with each
    updated column at a version that could be current in the interval.
    `columns` maps a column to (its versions, acknowledged when the read
    was sent, sent when it was answered)."""
    if not isinstance(got, list) or len(got) != len(base):
        return "not a row of the loaded width"
    for col, want in enumerate(base):
        if col in columns:
            versions, lo, hi = columns[col]
            if got[col] in versions[lo:hi + 1]:
                continue
            if got[col] in versions:
                return (f"column {col} at version "
                        f"{versions.index(got[col])}, outside [{lo}, {hi}]")
            return f"column {col} matches no version ever written"
        if got[col] != want:
            return f"column {col} differs from the row as loaded"
    return None

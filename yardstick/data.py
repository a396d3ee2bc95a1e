"""Rows and updates, from `--seed` alone.

The row is the reference's 8-column schema (`client.conf:50-61`):
`[OPE, CHE, PSSE, MSE, CHE, CHE, CHE, None]`, on the wire as its client
puts it there: OPE an int, PSSE and MSE decimal strings, the rest base64
strings. Only the PSSE (Paillier) and MSE (RSA) columns hold real
ciphertexts, made with python ints at one or two modmuls a row (the
generator of `chip_smoke.make_rows`, copied): row i's obfuscator is
r0^n g^(i n), a different r for every row, two modexps in all.

An update of row i multiplies its current ciphertext by the encryption of
the column's step (see `check.py`), so version j of row i is known to the
harness without asking the store.
"""

from __future__ import annotations

import base64
import random

from yardstick import check, keys, reference

PSSE, MSE = 2, 3


def _b64(rng: random.Random, nbytes: int) -> str:
    return base64.b64encode(rng.randbytes(nbytes)).decode()


class Dataset:
    """K rows and every version of them written so far."""

    def __init__(self, seed: int, k: int, plain_bits: int = 16,
                 step_bits: int = 32, paillier_bits: int = 2048,
                 rsa_bits: int = 1024):
        rng = random.Random(seed)
        self.rng = rng
        self.k = k
        # the keys by the sizes the configuration names: a KeyError that
        # lists the sizes on file when there is none of that size
        self.paillier = pai = reference.Paillier(
            *keys.on_file(keys.PAILLIER, "Paillier", paillier_bits))
        self.rsa = rsa = reference.Rsa(
            *keys.on_file(keys.RSA, "RSA", rsa_bits))
        self.moduli = {PSSE: pai.n2, MSE: rsa.n}
        # obfuscator chain: rn <- rn * hop, one modmul per ciphertext
        self._rn = pai.obfuscator(rng.randrange(2, pai.n))
        self._hop = pai.obfuscator(rng.randrange(2, pai.n))
        step = 1 << step_bits
        if k << plain_bits > step:
            raise ValueError("update step does not exceed the plain total")
        mstep = 3
        self._bump = {PSSE: (1 + step * pai.n) % pai.n2,
                      MSE: rsa.encrypt(mstep)}
        plains = [rng.randrange(1 << plain_bits) for _ in range(k)]
        mplains = [rng.randrange(2, 1 << plain_bits) for _ in range(k)]
        self.schemes = {
            PSSE: check.Additive(sum(plains), step),
            MSE: check.Multiplicative(
                reference.fold(mplains, rsa.n), mstep, rsa.n),
        }
        self.rows = []
        # versions[col][i] = every ciphertext written to row i's column
        self.versions = {PSSE: [], MSE: []}
        for i in range(k):
            c = pai.encrypt(plains[i], self._next_rn())
            m = rsa.encrypt(mplains[i])
            self.versions[PSSE].append([str(c)])
            self.versions[MSE].append([str(m)])
            self.rows.append([rng.getrandbits(63), _b64(rng, 32), str(c),
                              str(m), _b64(rng, 32), _b64(rng, 32),
                              _b64(rng, 32), _b64(rng, 48)])
        self.keys: list[str] = []       # record keys, as PutSet named them
        # per column: updates sent / acknowledged, in all and per row
        self.sent = {PSSE: 0, MSE: 0}
        self.acked = {PSSE: 0, MSE: 0}
        self.row_acked = {PSSE: [0] * k, MSE: [0] * k}
        # WriteElement reads the row, changes one column and writes the row
        # back, so two updates of one row must never be in flight together,
        # whatever their columns
        self.busy: set[int] = set()                # rows with one in flight
        self.unsure: set[tuple[int, int]] = set()  # outcome never learned

    def _next_rn(self) -> int:
        self._rn = self._rn * self._hop % self.paillier.n2
        return self._rn

    # ---------------------------------------------------------- updates

    def begin_update(self, col: int, i: int) -> str:
        """The next version of row i's column, registered as sent."""
        cur = int(self.versions[col][i][-1])
        new = cur * self._bump[col] % self.moduli[col]
        if col == PSSE:
            new = new * self._next_rn() % self.moduli[col]
        self.versions[col][i].append(str(new))
        self.busy.add(i)
        self.sent[col] += 1
        return str(new)

    def end_update(self, col: int, i: int, acknowledged: bool) -> None:
        if acknowledged:
            self.busy.discard(i)
            self.row_acked[col][i] += 1
            self.acked[col] += 1
        else:
            # it may or may not have been applied: never touch the row
            # again, and accept either version of it from now on
            self.unsure.add((col, i))

    def free(self, i: int) -> bool:
        return i not in self.busy

    def row_sent(self, col: int, i: int) -> int:
        return len(self.versions[col][i]) - 1

    def row_version(self, i: int, jp: int, jm: int) -> list:
        row = list(self.rows[i])
        row[PSSE] = self.versions[PSSE][i][jp]
        row[MSE] = self.versions[MSE][i][jm]
        return row

    def current(self, col: int) -> list[int]:
        """The column as the store holds it once every write is settled
        (rows whose last update was never acknowledged are left out by
        the caller: see `unsure`)."""
        return [int(v[self.row_acked[col][i]])
                for i, v in enumerate(self.versions[col])]

    def decrypt(self, col: int, c: int) -> int:
        return (self.paillier.decrypt_crt(c) if col == PSSE
                else self.rsa.decrypt(c))

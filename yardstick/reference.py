"""The plain reference: python ints only, nothing of the program.

What the served answers are held to. A Paillier ciphertext of m under
obfuscator r is (1 + m n) r^n mod n^2; the homomorphic sum of a column is
the modular product of its ciphertexts; an RSA ciphertext is m^e mod n and
the homomorphic product is again the modular product. This file knows those
four sentences and imports nothing from `dds_tpu`.
"""

from __future__ import annotations

import functools
import math


def fold(ciphertexts, modulus: int) -> int:
    """prod(ciphertexts) mod modulus: what SumAll / MultAll must return."""
    return functools.reduce(lambda a, b: a * b % modulus, ciphertexts,
                            1 % modulus)


class Paillier:
    """Paillier with g = n + 1."""

    def __init__(self, p: int, q: int):
        self.p, self.q = p, q
        self.n = p * q
        self.n2 = self.n * self.n
        self.lam = math.lcm(p - 1, q - 1)
        self.mu = pow(self.lam, -1, self.n)
        # CRT halves for decrypt_crt: h_x = L_x(g^(x-1) mod x^2)^-1 mod x
        self._p2, self._q2 = p * p, q * q
        self._hp = pow((pow(self.n + 1, p - 1, self._p2) - 1) // p, -1, p)
        self._hq = pow((pow(self.n + 1, q - 1, self._q2) - 1) // q, -1, q)
        self._qinv = pow(q, -1, p)

    def obfuscator(self, r: int) -> int:
        """r^n mod n^2, the randomising factor of one ciphertext."""
        return pow(r, self.n, self.n2)

    def encrypt(self, m: int, rn: int) -> int:
        """Enc(m) with a ready obfuscator rn = r^n: two modmuls."""
        return (1 + m * self.n) % self.n2 * rn % self.n2

    def decrypt(self, c: int) -> int:
        """The textbook decryption: L(c^lambda mod n^2) mu mod n."""
        return (pow(c, self.lam, self.n2) - 1) // self.n * self.mu % self.n

    def decrypt_crt(self, c: int) -> int:
        """The same plaintext by the two half-width exponentiations."""
        mp = (pow(c % self._p2, self.p - 1, self._p2) - 1) // self.p \
            * self._hp % self.p
        mq = (pow(c % self._q2, self.q - 1, self._q2) - 1) // self.q \
            * self._hq % self.q
        return mq + (mp - mq) * self._qinv % self.p * self.q


class Rsa:
    """Textbook RSA, the multiplicative column."""

    def __init__(self, p: int, q: int, e: int):
        self.n, self.e = p * q, e
        self.d = pow(e, -1, math.lcm(p - 1, q - 1))

    def encrypt(self, m: int) -> int:
        return pow(m, self.e, self.n)

    def decrypt(self, c: int) -> int:
        return pow(c, self.d, self.n)

"""The plain reference against known vectors and against its own
definitions computed another way."""

import random

from yardstick import keys, reference


def test_paillier_known_vector():
    # p = 11, q = 13: n = 143, n^2 = 20449, g = 144. By the definition
    # c = g^m r^n mod n^2 with m = 42, r = 23, worked by hand-checkable
    # repeated squaring: pow(144, 42, 20449) * pow(23, 143, 20449) % 20449
    pai = reference.Paillier(11, 13)
    c = pow(144, 42, 20449) * pow(23, 143, 20449) % 20449
    assert pai.encrypt(42, pai.obfuscator(23)) == c
    assert pai.decrypt(c) == 42
    assert pai.decrypt_crt(c) == 42


def test_paillier_sum_is_a_product_at_full_width():
    pai = reference.Paillier(*keys.PAILLIER[2048])
    assert pai.n.bit_length() == 2048
    rng = random.Random(7)
    plains = [rng.randrange(1 << 16) for _ in range(9)]
    cs = [pai.encrypt(m, pai.obfuscator(rng.randrange(2, pai.n)))
          for m in plains]
    total = reference.fold(cs, pai.n2)
    assert pai.decrypt(total) == sum(plains)
    assert pai.decrypt_crt(total) == sum(plains)
    # the shortcut (1 + m n) is g^m for g = n + 1
    assert (1 + plains[0] * pai.n) % pai.n2 == pow(pai.n + 1, plains[0],
                                                   pai.n2)


def test_fold_is_the_plain_loop():
    rng = random.Random(3)
    m = 0xFFFFFFFFFFFFFFC5
    cs = [rng.randrange(1, m) for _ in range(50)]
    want = 1
    for c in cs:
        want = want * c % m
    assert reference.fold(cs, m) == want
    assert reference.fold([], m) == 1


def test_rsa_known_vector_and_product():
    # the textbook key p = 61, q = 53, e = 17: 65 -> 2790 -> 65
    rsa = reference.Rsa(61, 53, 17)
    assert rsa.encrypt(65) == 2790
    assert rsa.decrypt(2790) == 65
    big = reference.Rsa(*keys.RSA[1024])
    assert big.n.bit_length() == 1024
    cs = [big.encrypt(m) for m in (3, 5, 7, 11)]
    assert big.decrypt(reference.fold(cs, big.n)) == 3 * 5 * 7 * 11

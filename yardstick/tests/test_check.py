"""The interval check: every legal interleaving passes; a stale, a torn
and an off-by-one-limb answer do not."""

import itertools

import pytest

from yardstick import check, reference
from yardstick.data import MSE, PSSE, Dataset


@pytest.fixture(scope="module")
def data():
    return Dataset(seed=5, k=6)


def _aggregate(d, col, versions_of_rows):
    return reference.fold(
        [int(d.versions[col][i][j]) for i, j in enumerate(versions_of_rows)],
        d.moduli[col])


@pytest.mark.parametrize("col", [PSSE, MSE])
def test_every_legal_interleaving_is_accepted(col):
    d = Dataset(seed=9, k=4)
    # rows 0 and 1 were updated and acknowledged before the aggregate was
    # sent; rows 2 and 3 have an update in flight while it runs
    for i in (0, 1):
        d.begin_update(col, i)
        d.end_update(col, i, True)
    lo = d.acked[col]
    for i in (2, 3):
        d.begin_update(col, i)
    hi = d.sent[col]
    assert (lo, hi) == (2, 4)
    for v2, v3 in itertools.product((0, 1), repeat=2):
        got = _aggregate(d, col, [1, 1, v2, v3])
        plain = d.decrypt(col, got)
        assert check.judge_aggregate(d.schemes[col], plain, lo, hi,
                                     d.sent[col]) is None


def test_stale_torn_and_limb_errors_are_refused(data):
    d = data
    for i in (0, 1, 2):
        d.begin_update(PSSE, i)
        d.end_update(PSSE, i, True)
    lo = hi = d.acked[PSSE]
    scheme = d.schemes[PSSE]

    def verdict(c):
        return check.judge_aggregate(scheme, d.decrypt(PSSE, c), lo, hi,
                                     d.sent[PSSE])

    fresh = _aggregate(d, PSSE, [1, 1, 1, 0, 0, 0])
    assert verdict(fresh) is None
    # a stale read: row 2's acknowledged update is missing
    assert verdict(_aggregate(d, PSSE, [1, 1, 0, 0, 0, 0])).startswith("stale")
    # an update nobody sent
    d.versions[PSSE][3].append(str(int(d.versions[PSSE][3][0])
                                   * d._bump[PSSE] % d.moduli[PSSE]))
    assert "future" in verdict(_aggregate(d, PSSE, [1, 1, 1, 1, 0, 0]))
    d.versions[PSSE][3].pop()
    # torn: one operand left out of the product
    torn = reference.fold([int(d.versions[PSSE][i][1]) for i in (0, 1, 2)]
                          + [int(d.versions[PSSE][i][0]) for i in (3, 4)],
                          d.moduli[PSSE])
    assert verdict(torn).startswith("torn")
    # one 16-bit limb off by one, and the result truncated by a limb
    assert verdict(fresh ^ (1 << (16 * 7))).startswith("torn")
    assert verdict(fresh % (1 << (16 * 255))).startswith("torn")


def test_rows_are_held_to_their_interval(data):
    d = Dataset(seed=11, k=3)
    d.begin_update(PSSE, 1)
    d.end_update(PSSE, 1, True)
    d.begin_update(PSSE, 1)          # version 2 in flight

    def verdict(row, lo, hi):
        return check.judge_row(
            row, d.rows[1], {PSSE: (d.versions[PSSE][1], lo, hi),
                             MSE: (d.versions[MSE][1], 0, 0)})

    assert verdict(d.row_version(1, 1, 0), 1, 2) is None
    assert verdict(d.row_version(1, 2, 0), 1, 2) is None
    assert "outside" in verdict(d.row_version(1, 0, 0), 1, 2)   # stale
    bad = d.row_version(1, 1, 0)
    bad[PSSE] = str(int(bad[PSSE]) ^ 1)
    assert "no version" in verdict(bad, 1, 2)
    bad = d.row_version(1, 1, 0)
    bad[4] = "AAAA"
    assert "differs from the row as loaded" in verdict(bad, 1, 2)
    assert verdict(d.row_version(1, 1, 0)[:-1], 1, 2) is not None


def test_an_unacknowledged_update_may_or_may_not_show():
    d = Dataset(seed=13, k=3)
    d.begin_update(PSSE, 0)
    d.end_update(PSSE, 0, False)     # the answer never came
    assert not d.free(0)             # and the row is left alone for good
    for seen in (0, 1):
        plain = d.decrypt(PSSE, _aggregate(d, PSSE, [seen, 0, 0]))
        assert check.judge_aggregate(d.schemes[PSSE], plain, d.acked[PSSE],
                                     d.sent[PSSE], d.sent[PSSE]) is None

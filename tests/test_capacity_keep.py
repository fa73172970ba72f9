"""The capacity a later split keeps, at its edges (``distribute.
shard_capacity`` with ``keep``), at the benchmark's own group capacity
(12775, 43118): a displaced split whose fullest group no longer fits
with ``REUSE_SLACK`` takes the NEXT rung, 64678, and not three times
that group (145528 and up: programs nobody has compiled, ROADMAP B11).
The fresh rule and the regrow are what they were."""
import pytest

from parmmg_tpu.parallel.distribute import (REUSE_SLACK, capacity_headroom,
                                            regrown_capacity, shard_capacity)

KEPT = (12775, 43118)


def test_the_slack_and_the_ladder_are_the_benchmarks():
    assert REUSE_SLACK == 1.25
    # the first cut of every grouped cell: 3 x the fullest group, up the
    # ladder (13,825 tets and 3,136 vertices in iso-scale6)
    assert shard_capacity(3136, 13825) == KEPT
    assert shard_capacity(2839, 12288) == (12775, 43118)
    assert shard_capacity(2838, 12288) == (8516, 43118)


@pytest.mark.parametrize("largest,cap_t", [
    (1, 43118), (31292, 43118), (34494, 43118),     # 1.25 x 34,494 fits
    (34495, 64678), (51742, 64678),                 # the next rung
    (51743, 97018), (77614, 97018), (77615, 145528)])
def test_a_kept_tet_capacity_moves_up_by_the_rung(largest, cap_t):
    assert shard_capacity(3000, largest, keep=KEPT) == (12775, cap_t)


@pytest.mark.parametrize("largest,cap_p", [
    (1, 12775), (10220, 12775), (10221, 19163), (15330, 19163),
    (15331, 28745)])
def test_a_kept_vertex_capacity_moves_up_by_the_rung(largest, cap_p):
    assert shard_capacity(largest, 20000, keep=KEPT) == (cap_p, 43118)


def test_both_columns_move_each_by_its_own_shard():
    assert shard_capacity(10221, 34495, keep=KEPT) == (19163, 64678)


@pytest.mark.parametrize("largest,fresh", [(34495, 145528),
                                           (51742, 218293)])
def test_the_fresh_rule_and_the_regrow_stand(largest, fresh):
    """What the kept rule fell back to until PR 43 is still the rule of
    a split that is handed no capacity."""
    assert shard_capacity(3000, largest) == (12775, fresh)
    assert shard_capacity(3000, largest, keep=None) == (12775, fresh)
    assert regrown_capacity(*KEPT) == (28745, 97018)


@pytest.mark.parametrize("most_verts,largest,room", [
    (3136, 13825, 100.0 * (1.0 - 1.25 * 13825 / 43118)),    # tets bind
    (3000, 34494, 100.0 * (1.0 - 1.25 * 34494 / 43118)),
    (10220, 20000, 0.0),                                # vertices bind
])
def test_headroom_is_nought_at_the_edge_the_kept_rule_has(
        most_verts, largest, room):
    got = capacity_headroom(most_verts, largest, *KEPT)
    assert got == pytest.approx(room, abs=1e-9)
    # at or above 0 the capacity is kept, under it it is not
    assert (got >= 0.0) == (
        shard_capacity(most_verts, largest, keep=KEPT) == KEPT)
    assert capacity_headroom(3000, 34495, *KEPT) < 0.0

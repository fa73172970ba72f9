"""``surf_list_share`` (PR 40), as test_polish_incr_share.py does for
PR 38's reader: a value where the two surface-list counters are there,
None on a program that lacks them or listed nothing."""
from byname import load
from test_layer_readers import grouped_job, run_of


def with_lists(listed, full, shift=0.0):
    j = grouped_job(shift)
    j["counters"].update({"surf.listed": listed, "surf.list_full": full})
    return j


def test_surf_list_share_is_listed_over_full_width_a_job():
    reader = load("layer_metrics", "surf_list_share")
    # iso-growth, CPU seed 21: 24 blocks of two rows of 52 x 43,118
    assert reader.read(run_of([with_lists(798686.0, 107622528.0)])) == \
        100.0 * 798686.0 / 107622528.0
    run = run_of([with_lists(1.0e6, 1.0e8),
                  with_lists(3.0e6, 1.0e8, shift=7.0)])
    assert reader.read(run) == 2.0
    # lists that held nothing: a value, not None
    assert reader.read(run_of([with_lists(0.0, 1.0e8)])) == 0.0


def test_surf_list_share_is_none_where_nothing_was_listed():
    reader = load("layer_metrics", "surf_list_share")
    # the program before PR 40: the other surf.* counters and no such two
    assert reader.read(run_of([grouped_job()])) is None
    assert reader.read(run_of([])) is None
    # a program that ran its scatters at full width publishes two zeros
    assert reader.read(run_of([with_lists(0.0, 0.0)])) is None
    half = grouped_job()
    half["counters"]["surf.list_full"] = 1.0e8
    assert reader.read(run_of([half])) is None

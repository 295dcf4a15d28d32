"""work_model's bytes for a case worked by hand."""

from chipbench import work_model as wm


def test_merge_bytes_by_hand():
    # 16 B key + 8 B seq + 1 B type + 8 B value = 33 B a row
    assert wm.row_bytes(16, 8) == 33
    # 1,000 rows in, 800 out, 10 bloom bits a key: 33,000 + 26,400 + 1,000
    assert wm.merge_bytes(1000, 800, 16, 8, 10) == 60400


def test_unit_rows_of_both_kinds_of_configuration():
    counter = {"rows_per_slot": 20000, "live_counters": True,
               "key_bytes": 16, "value_bytes": 8,
               "options": {"bits_per_key": 10}}
    # 20,000 bulk + 4,000 hit once + 1,000 hit again + 3 x 250 increments
    # on live-only counters + 125 base PUTs under half of them
    assert wm.unit_rows(counter) == (25875, 20250)
    assert wm.unit_bytes(counter) == 25875 * 33 + 20250 * 33 + 25313
    plain = dict(counter, rows_per_slot=4096, live_counters=False)
    assert wm.unit_rows(plain) == (4096, 4096)
    assert wm.unit_bytes(plain) == 2 * 4096 * 33 + 5120


def test_unit_rows_agree_with_the_generator():
    from chipbench import workload as wl

    ops = wl.preload_ops(7, 3, 20000)
    assert 20000 + len(ops) == 25875
    assert sum(1 for kind, _k, _v in ops if kind == wl.PUT) == 125
    assert len(wl.slot_model(7, 3, 20000, True)) == 20250


def test_the_controls_differ_from_the_reference_where_they_should():
    """``fold32`` differs on live-only counters alone (bulk PUTs are
    exact), ``bits32`` on nearly every key; sums carry and wrap."""
    from chipbench import workload as wl

    exact = wl.slot_model(7, 3, 2000, True)
    fold = wl.slot_model(7, 3, 2000, True, "fold32")
    bits = wl.slot_model(7, 3, 2000, True, "bits32")
    live = [wl.live_key(3, i) for i in range(wl.live_counters(2000))]
    bulk = [wl.bulk_key(3, i) for i in range(2000)]
    assert all(exact.get(k) == fold.get(k) for k in bulk)
    assert sum(exact.get(k) != fold.get(k) for k in live) > len(live) // 2
    assert sum(exact.get(k) != bits.get(k) for k in bulk) > 1900
    assert any(int.from_bytes(exact.get(k), "little") >> 63 for k in live)

"""``workload_rec``: the record unit's counts are the counter unit's, a
value is a pure function of (seed, slot, key, ordinal), the model applies
the unit in its order, and each control is a different answer."""

import numpy as np
import pytest

from chipbench import work_model, workload as wl, workload_rec as wr

SEED = 2**31 + 77


@pytest.mark.parametrize("rows", [256, 4096, 20480])
def test_unit_counts_are_workloads_own(rows):
    ops = wr.preload_ops(SEED, 3, rows)
    rows_in, rows_out = wl.unit_row_counts(rows, True)
    assert rows + len(ops) == rows_in
    assert len(ops) == len(wl.preload_ops(SEED, 3, rows))
    model = wr.slot_model(SEED, 3, rows, 64, True)
    assert len(model) == rows_out
    assert [n for _k, n in ops] == list(range(1, len(ops) + 1))
    # the same keys in the same arrival order as the counter unit's
    assert [k for k, _n in ops] == [k for _t, k, _v in
                                    wl.preload_ops(SEED, 3, rows)]


def test_the_configurations_unit_is_what_the_issue_counts():
    assert wl.unit_row_counts(20480, True) == (26496, 20736)
    assert len(wr.preload_ops(1, 0, 20480)) == 6016 == 47 * 128
    config = {"rows_per_slot": 20480, "live_counters": True,
              "key_bytes": 16, "value_bytes": 1024,
              "options": {"bits_per_key": 10}}
    width = 16 + 8 + 1 + 1024
    assert work_model.unit_bytes(config) == (
        26496 * width + 20736 * width + (20736 * 10 + 7) // 8)


def test_a_value_is_a_pure_function_of_its_four_arguments():
    key = wr.bulk_key(5, 123)
    v = wr.value(SEED, 5, key, 0, 1024)
    assert v == wr.value(SEED, 5, key, 0, 1024) and len(v) == 1024
    assert all(32 <= b <= 126 for b in v)
    others = {wr.value(SEED + 1, 5, key, 0, 1024),
              wr.value(SEED, 6, wr.bulk_key(6, 123), 0, 1024),
              wr.value(SEED, 5, wr.bulk_key(5, 124), 0, 1024),
              wr.value(SEED, 5, key, 1, 1024),
              wr.value(SEED, 5, wr.live_key(5, 123), 0, 1024)}
    assert len(others) == 5 and v not in others
    assert wr.value(SEED, 5, key, 0, 1000) == v[:1000]
    assert len(wr.value(SEED, 5, key, 0, 13)) == 13


def test_bulk_matrix_is_the_single_values():
    rows = wr.bulk_rows(SEED, 2, 300, 100)
    matrix = wr.bulk_values(SEED, 2, 300, 100)
    assert matrix.shape == (300, 100) and matrix.dtype == np.uint8
    for i in (0, 1, 150, 299):
        assert rows[i] == (wr.bulk_key(2, i), matrix[i].tobytes())
        assert rows[i][1] == wr.value(SEED, 2, wr.bulk_key(2, i), 0, 100)
    # the field is not a run of one byte: zlib level 1 leaves most of it
    import zlib

    raw = wr.bulk_values(SEED, 2, 30, 1024).tobytes()
    assert 0.75 < len(zlib.compress(raw, 1)) / len(raw) < 0.95


def test_model_bulk_shadows_live_and_live_only_keeps_its_newest():
    rows = 400
    ops = wr.preload_ops(SEED, 1, rows)
    model = wr.slot_model(SEED, 1, rows, 128, True)
    for key, _n in ops:
        if key[5:8] == b"key":  # overwritten, then shadowed by the load
            assert model.get(key) == wr.value(SEED, 1, key, 0, 128)
    for i in range(wl.live_counters(rows)):
        key = wr.live_key(1, i)
        newest = max(n for k, n in ops if k == key)
        assert sum(1 for k, _ in ops if k == key) == (4 if i % 2 == 0 else 3)
        assert model.get(key) == wr.value(SEED, 1, key, newest, 128)
    assert model.get(wr.absent_key(1, 0)) is None


@pytest.mark.parametrize("control", wr.CONTROLS)
def test_each_control_is_another_answer(control):
    rows = 400
    exact = wr.slot_model(SEED, 1, rows, 1024, True)
    fault = wr.slot_model(SEED, 1, rows, 1024, True, control)
    keys = wl.probe_keys(SEED, 1, rows, 64, True)
    differ = [k for k in keys if exact.get(k) != fault.get(k)]
    if control == "bits32":  # every value loses its tail
        assert len(differ) == sum(1 for k in keys if exact.get(k))
        k = differ[0]
        assert fault.get(k)[:8] == exact.get(k)[:8]
        assert fault.get(k)[8:] == bytes(1016)
    else:  # fold32: the first write of a key written twice survives
        assert differ and all(
            fault.get(k) != exact.get(k) and len(fault.get(k)) == 1024
            for k in differ)
        live = [k for k in differ if k[5:8] == b"liv"]
        overwritten = [k for k in differ if k[5:8] == b"key"]
        assert live and overwritten
    with pytest.raises(ValueError):
        wr.RecModel(1, 1, 8, "bits16")

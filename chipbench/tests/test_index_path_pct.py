"""``layers/index_path_pct`` on hand-written runs: the share by hand,
and nothing to read from a program that does not annotate its launches
(as the parent commit is) or from a run without launches."""

from chipbench.layers import index_path_pct
from chipbench.tests.test_layer_readers import run_of, span


def stream(shards, **annotations):
    return span("tpu.compact_stream", 50.0, shards=shards, group_size=8,
                capacity=32768, **annotations)


def test_share_of_launched_shards_by_hand():
    run = run_of([stream(1, value_path="index", val_words=256),
                  stream(7, value_path="index", val_words=256),
                  span("tpu.h2d", 3.0)])
    assert index_path_pct.read(run) == 100.0
    run = run_of([stream(1, value_path="ride", val_words=2),
                  stream(7, value_path="index", val_words=256)])
    assert index_path_pct.read(run) == 100.0 * 7 / 8
    run = run_of([stream(3, value_path="ride", val_words=2)])
    assert index_path_pct.read(run) == 0.0


def test_nothing_to_read():
    assert index_path_pct.read(run_of([span("tpu.h2d", 3.0)])) is None
    # the parent's spans carry shards and group_size and no value_path
    assert index_path_pct.read(run_of([stream(7), stream(1)])) is None

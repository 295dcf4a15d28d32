"""Layer compaction seam: the real value bytes of the window's launched
places over the bytes their rows take at the launch's value width (each
place's rows x the group's value words x 4): the ``value_bytes`` and
``value_bytes_padded`` annotations of the window's
``tpu.compact_stream`` spans, summed. Where values differ in width (db_bench
MixGraph's 10 to 1,024 bytes, ~37 on average) every row is padded to the
bucket of the widest, so this is the share of the moved value bytes that
are values, and the rest is what a ragged value move would save. A
program that does not say (the annotations are this metric's own) gives
nothing to read."""


def read(run):
    real = padded = 0
    for s in run.spans:
        if s["name"] != "tpu.compact_stream":
            continue
        ann = s["annotations"]
        if "value_bytes" not in ann or "value_bytes_padded" not in ann:
            return None
        real += int(ann["value_bytes"])
        padded += int(ann["value_bytes_padded"])
    return 100.0 * real / padded if padded else None

"""Layer host codecs: blocks decoded per scan, the ``blocks`` annotation
summed over the window's ``storage.scan`` spans over their count. The
engagement of the seek: a scan of up to 100 records decodes 1 to 3
blocks of a file of 64-row blocks, where reading the whole version
decodes every block of every file. A program without the span gives
nothing to read."""


def read(run):
    blocks = [s["annotations"]["blocks"] for s in run.spans
              if s["name"] == "storage.scan"
              and "blocks" in s["annotations"]]
    return sum(blocks) / len(blocks) if blocks else None

"""Layer wire: mean ``queue_wait_ms`` of the window's ``rpc.server.*``
roots (one per served RPC): frame received -> its dispatch starts, the
event loop's backlog the request sat behind, host clock."""


def read(run):
    ms = [s["annotations"]["queue_wait_ms"] for s in run.spans
          if s["name"].startswith("rpc.server.")
          and "queue_wait_ms" in s["annotations"]]
    return (sum(ms) / len(ms) or None) if ms else None

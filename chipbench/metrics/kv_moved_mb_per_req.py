"""KV bytes moved between the pools in the traced window, per request due
in the window: the `bytes` stat of every `exec.kv.copy` span (offloads
to the host tier, reloads, copy-on-write) and of every `exec.kv.write`
span into the host tier (a prefill's offloaded layers), in MB (1e6 B).
The bytes are the executor's own, blocks times the pool's block
size."""


def value(run):
    # the spans come from `spantrace.reduce_dir`; `trace.reduce_dir`
    # gives none
    spans = getattr(run.trace, "spans", None) or {}
    copy = spans.get("exec.kv.copy")
    write = spans.get("exec.kv.write")
    counted = run.counted()
    if (copy is None and write is None) or not counted:
        return None
    moved = (copy or {}).get("stats", {}).get("bytes", 0) \
        + (write or {}).get("by", {}).get("tier=host", {}).get("bytes", 0)
    return moved / 1e6 / len(counted)

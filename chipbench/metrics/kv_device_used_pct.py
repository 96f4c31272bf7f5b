"""Mean over the window's steps of the device KV pool's share in use,
1 - num_free(DEVICE) / pool, read from the block manager after each
step."""


def value(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.kv_used for s in steps) / len(steps)

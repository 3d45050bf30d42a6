"""scale * summed device time of the operations whose short name matches
`match` (a regular expression, see reduce_trace.short_op_name) /
counters[per], both over the traced slice."""


def read(obs, match, per, scale=1.0):
    trace = obs["trace"]
    if trace is None or not obs["counters"].get(per):
        return None
    seconds = trace.op_seconds(match)
    if seconds <= 0:
        return None
    return scale * seconds / obs["counters"][per]

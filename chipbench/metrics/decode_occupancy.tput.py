"""Engine: share of decode slot-steps that carried a token in the window
(decode_tokens / (decode_steps x slots), from the engine counters)."""


def read(rec):
    c = rec["counters"]
    if c["decode_steps"] <= 0:
        return None
    return 100.0 * c["decode_tokens"] / (c["decode_steps"] * rec["slots"])

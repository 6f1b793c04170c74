"""Engine host: device-idle milliseconds per engine tick whose innermost
span is one of the program's (``serve.*``: admit, chunk, grow, decode,
fetch, pick), from a trace reduced by ``spans.py``."""
from chipbench.spans import ms_per_tick


def read(rec):
    idle = (rec.get("trace") or {}).get("idle", {})
    return ms_per_tick(rec, sum(v for k, v in idle.items()
                                if k.startswith("serve.")))

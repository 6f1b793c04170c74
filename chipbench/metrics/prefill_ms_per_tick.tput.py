"""Integer forward, chunk program: device milliseconds of ``jit_prefill``
per engine tick (``serve.tick`` spans) in the traced window."""
from chipbench.spans import ms_per_tick


def read(rec):
    mods = (rec.get("trace") or {}).get("modules", {})
    return ms_per_tick(rec, mods.get("jit_prefill", {}).get("s", 0.0))

"""Integer forward, FFN activation lookup: device milliseconds per engine
tick of the ops under the ``mlp/act_lut`` named scope, every program."""
from chipbench.spans import ms_per_tick


def read(rec):
    scopes = (rec.get("trace") or {}).get("scopes", {})
    if "mlp/act_lut" not in scopes:
        return None
    return ms_per_tick(rec, scopes["mlp/act_lut"])

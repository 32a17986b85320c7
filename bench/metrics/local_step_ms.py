"""local_step_ms: device milliseconds per round under the round's
``bafdp.local_step`` scope (the local DRO/LDP step of every delivered
client: forward, backward, Adam, the eps proposal)."""

STAGE = "bafdp.local_step"


def read(record):
    stages = (record.get("trace") or {}).get("stage_time") or {}
    per_round = stages.get("per_round") or {}
    if STAGE not in per_round:
        return None
    return per_round[STAGE] * 1e3

"""fold_ms: device milliseconds per round under the round's
``bafdp.fold`` scope (the Eq. (20) fold: dual mean and sign consensus,
XLA and Pallas ops alike)."""

STAGE = "bafdp.fold"


def read(record):
    stages = (record.get("trace") or {}).get("stage_time") or {}
    per_round = stages.get("per_round") or {}
    if STAGE not in per_round:
        return None
    return per_round[STAGE] * 1e3

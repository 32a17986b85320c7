"""Operations and bytes from shapes: the numerators of the per-layer shares.

All counts follow the forecaster of a configuration file's ``model`` block
(an MLP ``d_x -> hidden... -> horizon``) and a round's delivered rows.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

F32 = 4


def layer_dims(model: Dict) -> List[Tuple[int, int]]:
    """(fan_in, fan_out) of each dense layer of the MLP forecaster."""
    d_x = (model["closeness_len"] + model["period_len"] + model["n_meta"]
           + model["n_text"])
    dims = [d_x] + list(model["hidden"]) + [model["horizon"]]
    return list(zip(dims[:-1], dims[1:]))


def d_x(model: Dict) -> int:
    return layer_dims(model)[0][0]


def leaf_sizes(model: Dict) -> List[int]:
    """Element counts of the parameter leaves, one (w, b) pair per layer."""
    out = []
    for fi, fo in layer_dims(model):
        out += [fi * fo, fo]
    return out


def n_params(model: Dict) -> int:
    return sum(leaf_sizes(model))


def update_flops(model: Dict, batch: int, local_steps: int = 1) -> int:
    """Matrix-multiply FLOPs of one client update: forward and backward
    of the forecaster over ``batch`` rows, ``local_steps`` times.

    Forward 2*b*fi*fo per layer; backward 2*b*fi*fo for the weight
    gradient of every layer and 2*b*fi*fo for the input gradient of every
    layer but the first (the data need no gradient).  Bias adds, the
    activation and the Lipschitz surrogate's power iterations are left
    out: this is the model's work, not the round's.
    """
    dims = layer_dims(model)
    macs = sum(fi * fo for fi, fo in dims)
    first = dims[0][0] * dims[0][1]
    return local_steps * 2 * batch * (2 * macs + (macs - first))


def round_min_bytes(model: Dict, n_distinct: int, n_clients: int,
                    batch: int) -> int:
    """The least bytes any correct sparse round must move in HBM.

    Per delivered client: read and write its row of each of the five
    per-client leaves the round updates (W, z_local, phi, Adam m and v),
    and read its batch rows (x and y).  Per round: read and write the
    consensus z, and read eps and read and write lambda for all C clients
    (Eq. 21 updates every lambda).  Nothing the program could skip is
    counted, so the share of a peak cannot pass 100%.
    """
    p = n_params(model)
    per_client = 2 * 5 * p * F32 + batch * (d_x(model) + model["horizon"]) \
        * F32
    return n_distinct * per_client + 2 * p * F32 + 3 * n_clients * F32


def fold_min_bytes(model: Dict, s_max: int) -> int:
    """Least HBM bytes of the Eq. (20) fold over one round, all leaves:
    per leaf of D elements it reads the (S_max, D) message block, z and
    the mean dual and writes z' ((S_max + 3) * D * 4 bytes), and reads
    the (S_max,) weight column."""
    return sum((s_max + 3) * d * F32 + s_max * F32
               for d in leaf_sizes(model))


def n_leaves(model: Dict) -> int:
    return len(leaf_sizes(model))


def window_totals(model: Dict, rows: Sequence[Tuple[int, int]],
                  n_clients: int, batch: int, local_steps: int = 1
                  ) -> Dict[str, int]:
    """FLOPs and least bytes of a window of rounds; ``rows`` holds
    (deliveries, distinct clients) per round."""
    upd = sum(k for k, _ in rows)
    return {
        "updates": upd,
        "flops": upd * update_flops(model, batch, local_steps),
        "bytes": sum(round_min_bytes(model, n, n_clients, batch)
                     for _, n in rows),
    }

"""Plain reference of the first rounds of BAFDP over an MLP fleet.

Independent of the program: it imports nothing from ``repro`` or
``benchmarks`` and takes nothing the program made.  From a configuration
file, the run's seed and the schedule's first padded rows (the traffic,
an input) it rebuilds

* the synthetic city traffic, its windows and per-client min-max scaling
  (vectorised numpy; the same random stream as a per-client loop),
* each round's per-client minibatch draw,
* the clients' initial forecasters and the federated state,

and runs the paper's round (Algorithm 1, Eq. 15-22) on the delivered
rows in plain ``jax.numpy`` at ``float32`` with ``highest`` matmul
precision: the local DRO objective with input-level LDP noise, Adam, the
Eq. (19) eps step, the Eq. (20) sign fold, the Eq. (21) lambda step, the
Eq. (22) dual step and the write-back of the delivered rows.

:func:`readings` returns what the check compares: each round's loss, the
first gradient's norm per leaf, and per leaf the norm of each state
variable's change from after round 1 to after round 3.  ``dtype`` puts
the whole computation in another precision (the control), and ``fault``
plants one of the faults the check has to catch.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import layer_dims

N_ROUNDS = 3
BLOCK = 2048          # delivered rows per local-step call
FAULTS = ("half_batch", "z_step_doubled")


# ---------------------------------------------------------------------------
# data: the synthetic city, its windows and the per-round minibatches
def _diurnal(h, ph):
    x = 2 * np.pi * (h - ph) / 24.0
    return 0.55 + 0.35 * np.sin(x - 2.2) + 0.18 * np.sin(2 * x + 0.5)


def city(fleet: Dict, seed: int) -> Dict[str, np.ndarray]:
    """traffic (C, T), text (C, T, 4), meta (T, 9), all float32."""
    rng = np.random.RandomState(
        seed + zlib.crc32(fleet["dataset"].encode()) % 10_000)
    T, C = fleet["n_hours"], fleet["n_clients"]
    t = np.arange(T)
    hour, day = t % 24, t // 24
    dow = (day + fleet["start_dow"]) % 7
    weekend = dow >= 5
    holiday = np.isin(day, np.asarray(fleet["holidays"]))

    base = fleet["scale"] * np.exp(0.6 * rng.randn(C))
    phase = rng.uniform(-2, 2, C)
    wk_ratio = 1 - fleet["weekend_dip"] * rng.uniform(0.6, 1.4, C)
    evt_sens = rng.uniform(0.3, 1.7, C)
    n_events = max(3, T // 200)
    events = np.zeros(T)
    for et in rng.choice(T, n_events, replace=False):
        amp = rng.uniform(0.5, 1.0)
        width = rng.uniform(2, 6)
        events += amp * np.exp(-0.5 * ((t - et) / width) ** 2)

    d = _diurnal(hour[None, :], phase[:, None])
    wk = np.where(weekend[None, :], wk_ratio[:, None], 1.0)
    hol = np.where(holiday, 0.75, 1.0)[None, :]
    lam = base[:, None] * d * wk * hol \
        * (1 + fleet["burstiness"] * evt_sens[:, None] * events[None, :])
    traffic = np.maximum(lam * (1 + fleet["noise"] * rng.randn(C, T)), 0.0)

    tweets = (20 + 80 * _diurnal(hour, 0)) * (1 + 2.0 * events)
    users = 0.7 * tweets * (1 + 0.1 * rng.randn(T))
    news = np.repeat(5 + 10 * events.reshape(-1, 24).mean(1), 24)[:T]
    geo = (10 + 30 * _diurnal(hour, 1.0)) * (1 + events)
    text_city = np.stack([tweets, users, news, geo], axis=-1)
    text = text_city[None] * (1 + 0.15 * rng.randn(C, T, 4))

    meta = np.zeros((T, 9))
    meta[t, dow] = 1.0
    meta[:, 7] = holiday
    meta[:, 8] = hour / 23.0
    return {"traffic": traffic.astype(np.float32),
            "text": text.astype(np.float32),
            "meta": meta.astype(np.float32)}


def train_windows(data: Dict[str, np.ndarray], model: Dict,
                  test_days: int) -> Tuple[np.ndarray, np.ndarray]:
    """Scaled training windows x (C, N, d_x) and y (C, N, H) (Sec. III-B:
    closeness and period windows, metadata, text; min-max scaled per
    client on the training span)."""
    traffic, text, meta = data["traffic"], data["text"], data["meta"]
    C, T = traffic.shape
    cl, pl, H = model["closeness_len"], model["period_len"], model["horizon"]
    start = max(cl, pl * 24)
    ts = np.arange(start, T - H + 1)
    n = ts.size
    n_test = (test_days * 24) - H + 1 if H > 1 else test_days * 24
    split = n - min(n_test, n - 1)
    ts = ts[:split]
    x = np.concatenate([
        traffic[:, ts[:, None] - cl + np.arange(cl)[None, :]],
        traffic[:, ts[:, None] - 24 * np.arange(pl, 0, -1)[None, :]],
        np.broadcast_to(meta[ts][None], (C, split, meta.shape[1])),
        text[:, ts - 1, :model["n_text"]],
    ], axis=-1)
    y = traffic[:, ts[:, None] + np.arange(H)[None, :]]
    lo, hi = x.min(axis=1, keepdims=True), x.max(axis=1, keepdims=True)
    span = hi - lo
    den = np.where(span < 1e-6, np.float32(1.0), span)
    x = (x - lo) / den
    y = (y - lo[..., :1]) / den[..., :1]
    return x.astype(np.float32), y.astype(np.float32)


def batch_draws(n_clients: int, n_train: int, batch: int, seed: int,
                n_rounds: int) -> List[np.ndarray]:
    """Per-round (C, b) window indices: one ``randint`` per round from a
    RandomState seeded with the run's seed."""
    rng = np.random.RandomState(seed)
    return [rng.randint(0, n_train, size=(n_clients, batch))
            for _ in range(n_rounds)]


# ---------------------------------------------------------------------------
# model
def init_client(key, dims, dtype):
    ks = jax.random.split(key, len(dims))
    return {f"l{i}": {"w": ((1.0 / math.sqrt(fi)) * jax.random.truncated_normal(
                          ks[i], -2.0, 2.0, (fi, fo))).astype(dtype),
                      "b": jnp.zeros((fo,), dtype)}
            for i, (fi, fo) in enumerate(dims)}


def mlp(p, x):
    n = len(p)
    for i in range(n):
        x = x @ p[f"l{i}"]["w"] + p[f"l{i}"]["b"]
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x


def spectral_norm(w, iters: int):
    v = jnp.full((w.shape[1],), 1.0 / math.sqrt(w.shape[1]), w.dtype)
    for _ in range(iters):
        u = w @ v
        u = u / jnp.maximum(jnp.linalg.norm(u), 1e-9)
        v = w.T @ u
        v = v / jnp.maximum(jnp.linalg.norm(v), 1e-9)
    return jnp.dot(u, w @ v)


def lipschitz(p, iters: int):
    """Product of the weight matrices' spectral norms, through logs."""
    s = sum(jnp.log(jnp.maximum(spectral_norm(p[k]["w"], iters), 1e-6))
            for k in sorted(p))
    return jnp.exp(jnp.clip(s, -20.0, 20.0))


# ---------------------------------------------------------------------------
# the round
def constants(cfg: Dict, n_train: int) -> Dict[str, float]:
    """c3 of the Gaussian mechanism and the Wasserstein radius eta."""
    fed, tr, model = cfg["fed"], cfg["training"], cfg["model"]
    d = layer_dims(model)[0][0] + model["horizon"]
    c3 = math.sqrt(2.0 * d * math.log(1.25 / fed["dp_delta"])) \
        * tr["c3_sensitivity"]
    log_term = math.log(tr["fournier_guillin_c1"] / fed["confidence_gamma"])
    expo = 1.0 / max(d, 2) if n_train >= log_term / tr["fournier_guillin_c2"] \
        else 1.0 / fed["wasserstein_beta"]
    eta = (log_term / (tr["fournier_guillin_c2"] * n_train)) ** expo
    return {"c3": c3, "eta": eta}


class Settings(NamedTuple):
    """The scalars the round reads, hashable so its jitted pieces are
    compiled once per configuration."""
    n_clients: int
    input_sigma: float
    spectral_iters: int
    eta: float
    c3: float
    eps_min: float
    dro_weight: float
    adam_b1: float
    adam_b2: float
    adam_eps: float
    psi: float
    alpha_w: float
    alpha_eps: float
    alpha_z: float
    alpha_phi: float
    privacy_budget_a: float
    reg_decay_pow: float
    fault: Optional[str]


def settings(cfg: Dict, n_train: int, fault: Optional[str]) -> Settings:
    fed, tr = cfg["fed"], cfg["training"]
    k = constants(cfg, n_train)
    return Settings(
        n_clients=cfg["fleet"]["n_clients"], input_sigma=tr["input_sigma"],
        spectral_iters=tr["spectral_iters"], eta=k["eta"], c3=k["c3"],
        fault=fault, **{f: fed[f] for f in Settings._fields
                        if f in fed})


def _mask(valid, a):
    return jnp.where(valid.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0)


@functools.partial(jax.jit, static_argnums=0)
def _local_block(s: Settings, z, W, zl, phi, m, v, cnt, eps, lam, x, y, keys,
                 valid):
    """Step 1 of Algorithm 1 for a block of delivered rows, with the
    block's share of the Eq. (20) sums (``valid`` masks padding rows)."""
    dt = x.dtype
    if s.fault == "half_batch":
        x, y = x[:, : x.shape[1] // 2], y[:, : y.shape[1] // 2]

    def objective(w, xi, yi, key, e):
        sigma = (s.input_sigma / jnp.maximum(e, s.eps_min)).astype(dt)
        xt = xi + jax.random.normal(key, xi.shape, dt) * sigma
        g = jnp.mean(jnp.square(mlp(w, xt) - yi))
        G = lipschitz(w, s.spectral_iters)
        rho = s.dro_weight * (s.eta + s.c3 / jnp.maximum(e, s.eps_min))
        return g + rho * G, G

    (loss, G), grads = jax.vmap(jax.value_and_grad(objective, has_aux=True))(
        W, x, y, keys, eps)
    b1, b2 = s.adam_b1, s.adam_b2
    cnt = cnt + 1
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    bc1 = (1 - b1 ** cnt.astype(jnp.float32)).astype(dt)
    bc2 = (1 - b2 ** cnt.astype(jnp.float32)).astype(dt)

    def step(w, z_l, p, m_l, v_l):
        r = (-1,) + (1,) * (w.ndim - 1)
        adam = (m_l / bc1.reshape(r)) / (jnp.sqrt(v_l / bc2.reshape(r))
                                         + s.adam_eps)
        lag = s.psi * jnp.sign(w - z_l) - p
        return w - s.alpha_w * (adam + lag)

    W_new = jax.tree.map(step, W, zl, phi, m, v)
    d_eps = -s.dro_weight * s.c3 * G / jnp.square(jnp.maximum(eps, s.eps_min)) \
        + lam
    eps_new = jnp.clip(eps - s.alpha_eps * d_eps, s.eps_min,
                       s.privacy_budget_a)
    sums = {
        "loss": jnp.sum(_mask(valid, loss).astype(jnp.float32)),
        "grad_sq": jax.tree.map(lambda g: jnp.sum(jnp.square(
            _mask(valid, g).astype(jnp.float32))), grads),
        "signs": jax.tree.map(lambda z_l, w: jnp.sum(_mask(
            valid, jnp.sign(z_l[None] - w)), axis=0), z, W_new),
        "phi": jax.tree.map(lambda p: jnp.sum(_mask(valid, p), axis=0), phi),
    }
    return W_new, m, v, cnt, eps_new, sums


@functools.partial(jax.jit, static_argnums=0)
def _fold(s: Settings, z, signs, phi_sum):
    """Eq. (20): z' = z - alpha_z (sum phi / C + psi sum sign(z - w) / C)
    over the round's delivered messages."""
    def one(z_l, sg, ph):
        step = s.alpha_z * (ph / s.n_clients + s.psi * (sg / s.n_clients))
        if s.fault == "z_step_doubled":
            step = 2 * step
        return z_l - step
    return jax.tree.map(one, z, signs, phi_sum)


@functools.partial(jax.jit, static_argnums=0)
def _dual(s: Settings, t, z, phi, W):
    """Eq. (22) for a block of delivered rows, with a2 = 1/(alpha (t+1)^p)."""
    a2 = 1.0 / (s.alpha_phi * (t + 1.0) ** s.reg_decay_pow)
    return jax.tree.map(
        lambda p, z_l, w: p + s.alpha_phi * ((z_l[None] - w)
                                             - a2.astype(p.dtype) * p),
        phi, z, W)


def _norms(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(
        l.astype(jnp.float32).ravel())) for k, l in flat}


def _rows(tree, ids):
    return jax.tree.map(lambda l: l[ids], tree)


def _put(tree, ids, blk):
    return jax.tree.map(lambda f, b: f.at[ids].set(b.astype(f.dtype),
                                                   mode="drop"), tree, blk)


def readings(cfg: Dict, seed: int, rows: Sequence[Tuple[np.ndarray, ...]],
             dtype=jnp.float32, fault: str = None) -> Dict:
    """The compared readings of rounds 1 to 3; ``rows`` are the first
    three padded (idx, stale, weight) rows of the schedule."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    fed, model, fleet = cfg["fed"], cfg["model"], cfg["fleet"]
    unsupported = {k: fed[k] for k, want in (
        ("staleness_decay", "constant"), ("staleness_compensation", "none"),
        ("robust_consensus", "none"), ("local_steps", 1),
        ("omega_optimizer", "adam"), ("grad_clip", 0.0),
        ("byzantine_frac", 0.0), ("fedbuff_lr_norm", False),
        ("lipschitz_surrogate", "spectral")) if fed[k] != want}
    if unsupported:
        raise NotImplementedError(f"reference has no path for {unsupported}")
    C, B = fleet["n_clients"], cfg["training"]["batch"]
    x_all, y_all = train_windows(city(fleet, seed), model,
                                 fleet["test_days"])
    draws = batch_draws(C, x_all.shape[1], B, seed, N_ROUNDS)
    sett = settings(cfg, x_all.shape[1], fault)
    dims = layer_dims(model)
    key = jax.random.PRNGKey(seed)

    with jax.default_matmul_precision("highest"):
        W = jax.vmap(lambda k: init_client(k, dims, dtype))(
            jax.random.split(key, C))
        zeros = jax.tree.map(jnp.zeros_like, W)
        st = {"W": W, "z": jax.tree.map(lambda l: l[0], W),
              "zl": jax.tree.map(lambda l: jnp.broadcast_to(
                  l[0][None], l.shape), W),
              "phi": zeros, "m": zeros, "v": zeros,
              "cnt": jnp.zeros((C,), jnp.int32),
              "eps": jnp.full((C,), max(fed["privacy_budget_a"]
                                        * fed["eps_init_frac"],
                                        fed["eps_min"]), dtype),
              "lam": jnp.zeros((C,), dtype)}
        out = {"loss": [], "grad": None, "change": None}
        snap = None
        for t in range(N_ROUNDS):
            idx, _, weight = rows[t]
            ids = np.asarray(idx)[np.asarray(weight) > 0].astype(np.int64)
            n = ids.size
            blk = min(BLOCK, n)
            ids_p = np.concatenate([ids, np.full(-n % blk, ids[0])])
            valid = np.arange(ids_p.size) < n
            wid = np.where(valid, ids_p, C)        # padding never writes
            k_noise = jax.random.split(jax.random.fold_in(key, t), 3)[1]
            keys = jax.random.split(k_noise, C)[ids_p]
            draw = draws[t][ids_p]
            x = jnp.asarray(x_all[ids_p[:, None], draw], dtype)
            y = jnp.asarray(y_all[ids_p[:, None], draw], dtype)
            # pass 1: local steps block by block; the write-back of W, m,
            # v waits until every block has read the pre-round rows
            new, acc = {}, None
            for b in range(0, ids_p.size, blk):
                sl = slice(b, b + blk)
                ib = ids_p[sl]
                W_b, m_b, v_b, cnt_b, eps_b, sums = _local_block(
                    sett, st["z"], _rows(st["W"], ib), _rows(st["zl"], ib),
                    _rows(st["phi"], ib), _rows(st["m"], ib),
                    _rows(st["v"], ib), st["cnt"][ib], st["eps"][ib],
                    st["lam"][ib], x[sl], y[sl], keys[sl],
                    jnp.asarray(valid[sl]))
                new[b] = (W_b, m_b, v_b, cnt_b, eps_b)
                acc = sums if acc is None else jax.tree.map(
                    jnp.add, acc, sums)
            pre_phi = st["phi"]
            for b, (W_b, m_b, v_b, cnt_b, eps_b) in new.items():
                w = wid[b:b + blk]
                st["W"] = _put(st["W"], w, W_b)
                st["m"] = _put(st["m"], w, m_b)
                st["v"] = _put(st["v"], w, v_b)
                st["cnt"] = st["cnt"].at[w].set(cnt_b, mode="drop")
                st["eps"] = st["eps"].at[w].set(eps_b, mode="drop")
            del new
            z_new = _fold(sett, st["z"], acc["signs"], acc["phi"])
            # pass 2: the dual step and the z sync of the delivered rows
            for b in range(0, ids_p.size, blk):
                ib, w = ids_p[b:b + blk], wid[b:b + blk]
                phi_b = _dual(sett, t, z_new, _rows(pre_phi, ib),
                              _rows(st["W"], ib))
                st["phi"] = _put(st["phi"], w, phi_b)
                st["zl"] = _put(st["zl"], w, jax.tree.map(
                    lambda z_l: jnp.broadcast_to(z_l[None], (w.size,)
                                                 + z_l.shape), z_new))
            del pre_phi
            a1 = 1.0 / (fed["alpha_lambda"] * (t + 1.0) ** fed["reg_decay_pow"])
            st["lam"] = jnp.maximum(st["lam"] + fed["alpha_lambda"] * (
                (st["eps"] - fed["privacy_budget_a"]) - a1 * st["lam"]), 0)
            st["z"] = z_new
            out["loss"].append(float(acc["loss"]) / n)
            if t == 0:
                flat, _ = jax.tree_util.tree_flatten_with_path(
                    acc["grad_sq"])
                out["grad"] = {jax.tree_util.keystr(k): math.sqrt(float(v))
                               for k, v in flat}
                snap = {k: st[k] for k in ("W", "z", "phi", "eps", "lam")}
        out["change"] = change_norms(snap, st)
    return out


def change_norms(before: Dict, after: Dict) -> Dict[str, float]:
    """Per leaf, the norm of each state variable's change."""
    out = {}
    for name in ("W", "z", "phi", "eps", "lam"):
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                            - b.astype(jnp.float32), after[name],
                            before[name])
        for k, v in _norms(diff).items():
            out[f"{name}{k}"] = v
    return out

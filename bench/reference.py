"""The plain float64 reference of a tenant's search.

It imports nothing of the program and takes nothing the program made. It
rebuilds, from the configuration file and the run's seed alone, what a
Karasu tenant computes for each decision (paper §III):

- GP fits: Matern-5/2 with ARD lengthscales, fixed noise, targets
  standardised per fit; Adam on the negative log marginal likelihood,
  120 steps from zero on a model's first fit and 16 steps from its last
  fit after that (gradients by autodiff, in float64 on the CPU);
- support selection (Algorithm 1 of the paper): every shared run on the
  same machine type scores (Pearson(metrics) + 1) / 2, weighted by
  2^-|log2 nodes - log2 nodes'|, averaged per workload; the best
  ``n_support`` workloads;
- RGPE weights: ``rgpe_samples`` draws of each support model's marginal
  posterior at the tenant's configurations, and of the tenant model's
  leave-one-out posterior, scored by misranked pairs; weight = share of
  draws each model wins (ties split), support models whose median loss
  exceeds the 95th percentile of the tenant model's dropped; the draws'
  normal deviates come from the tenant's seed by the documented key
  schedule (``fold_in`` of purpose, iteration, index);
- the weighted mixture of standardised posteriors over the 69 configs;
- constrained EI (minimisation) for one objective, MC expected
  hypervolume improvement over the observed front for two.

A decision's acquisition is evaluated over the configurations the tenant
had not profiled.

A ``Variant`` computes the same search otherwise: the control, in float32
with the program's Gram and posterior products rounded as a TPU's
lower-precision matmul passes round them (the program states float32 at
``HIGHEST``), and the planted faults the check has to catch (a fit left
at its initial hyperparameters, Adam without its bias correction, RGPE
weights left uniform).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.scout import encode

JITTER = 1e-6
SQRT5 = math.sqrt(5.0)
VAR_FLOOR = 1e-12
PURPOSE_RGPE, PURPOSE_EHVI = 0, 1
R2_SHIFT = 1e-12     # the program's shift under the square root of d2


@dataclasses.dataclass(frozen=True)
class Variant:
    """How the search is computed. ``passes`` 0 is float64, exact; 3 is
    float32 with each product at ``high`` (three bfloat16 passes), 1 at
    ``default`` (one pass). ``fit``: "adam", "init" (never fitted) or
    "adam_nobias"; ``rgpe``: "ranked" or "uniform"."""
    passes: int = 0
    fit: str = "adam"
    rgpe: str = "ranked"

    @property
    def dtype(self):
        return np.float64 if self.passes == 0 else np.float32


EXACT = Variant()


# -- lower-precision products, as a TPU's matmul passes round them -------------

def bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest, ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def mm(a, b, passes: int = 0):
    """a @ b: exact for ``passes`` 0, else in float32 from bfloat16
    parts, hi*hi (+ hi*lo + lo*hi for three passes)."""
    if passes == 0:
        return a @ b
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = bf16(a), bf16(b)
    out = ah @ bh
    if passes == 3:
        out = out + ah @ bf16(b - bh) + bf16(a - ah) @ bh
    return out


def _mm_jax(a, b, passes: int):
    import jax.numpy as jnp
    if passes == 0:
        return a @ b

    def part(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)
    ah, bh = part(a), part(b)
    out = ah @ bh
    if passes == 3:
        out = out + ah @ part(b - bh) + part(a - ah) @ bh
    return out


# -- GP algebra, float64 numpy -------------------------------------------------

def matern(a, b, log_ls, log_sf, passes: int = 0):
    """Matern-5/2; a lower precision forms d2 as the program does,
    |a|^2 + |b|^2 - 2 a.b, with the product at that precision."""
    if passes == 0:
        ls = np.exp(np.asarray(log_ls, np.float64))
        diff = a[:, None, :] / ls - b[None, :, :] / ls
        d2 = np.sum(diff * diff, axis=-1)
        r = np.sqrt(d2)
        return math.exp(log_sf) * (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) \
            * np.exp(-SQRT5 * r)
    ls = np.exp(np.asarray(log_ls, np.float32))
    at = np.asarray(a, np.float32) / ls
    bt = np.asarray(b, np.float32) / ls
    d2 = np.maximum(np.sum(at * at, 1)[:, None] + np.sum(bt * bt, 1)[None]
                    - 2.0 * mm(at, bt.T, passes), 0.0)
    r = np.sqrt(d2 + np.float32(R2_SHIFT))
    return (np.float32(math.exp(log_sf))
            * (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) * np.exp(-SQRT5 * r)
            ).astype(np.float32)


def standardise(y):
    y = np.asarray(y, np.float64)
    mu = y.mean()
    sd = max(math.sqrt(np.mean((y - mu) ** 2)), 1e-8)
    return (y - mu) / sd, mu, sd


class GP64:
    """One fitted GP on standardised targets (float64 unless a lower
    ``passes`` is asked for)."""

    def __init__(self, x, y_raw, log_ls, log_sf, noise, passes: int = 0):
        self.passes = passes
        dt = np.float64 if passes == 0 else np.float32
        self.x = np.asarray(x, dt)
        y, self.mean, self.std = standardise(y_raw)
        self.y = y.astype(dt)
        self.log_ls = np.asarray(log_ls, dt)
        self.log_sf = float(log_sf)
        k = matern(self.x, self.x, self.log_ls, self.log_sf, passes) \
            + dt(noise + JITTER) * np.eye(len(self.x), dtype=dt)
        self.k = k
        self.chol = np.linalg.cholesky(k)
        self.alpha = np.linalg.solve(k, self.y)

    def posterior(self, xq):
        dt = self.x.dtype
        ks = matern(np.asarray(xq, dt), self.x, self.log_ls,
                    self.log_sf, self.passes)
        mu = mm(ks, self.alpha, self.passes)
        v = np.linalg.solve(self.chol, ks.T)
        return (np.asarray(mu, np.float64),
                np.maximum(math.exp(self.log_sf) - np.sum(v * v, 0),
                           1e-10).astype(np.float64))

    def loo(self):
        kinv = np.linalg.inv(self.k)
        d = np.diag(kinv)
        return self.y - self.alpha / d, np.maximum(1.0 / d, 1e-10)


def nlml(x, y_raw, log_ls, log_sf, noise) -> float:
    """Negative log marginal likelihood of standardised targets."""
    y, _, _ = standardise(y_raw)
    x = np.asarray(x, np.float64)
    k = matern(x, x, log_ls, log_sf) + (noise + JITTER) * np.eye(len(x))
    chol = np.linalg.cholesky(k)
    a = np.linalg.solve(chol, y)
    return float(0.5 * a @ a + np.sum(np.log(np.diag(chol)))
                 + 0.5 * len(x) * math.log(2.0 * math.pi))


# -- fitting: Adam on autodiff NLML, float64 on the CPU -------------------------

def _adam_fit(x, y, mask, init_ls, init_sf, *, steps, noise, lr,
              passes=0, bias=True):
    import jax
    import jax.numpy as jnp

    def loss(p):
        ls, sf = jnp.exp(p[0]), jnp.exp(p[1])
        xt = x / ls
        if passes:
            sq = jnp.sum(xt * xt, 1)
            d2 = jnp.maximum(sq[:, None] + sq[None, :]
                             - 2.0 * _mm_jax(xt, xt.T, passes), 0.0)
            r = jnp.sqrt(d2 + R2_SHIFT)
            mval = (1.0 + SQRT5 * r + 5.0 / 3.0 * d2) * jnp.exp(-SQRT5 * r)
        else:
            diff = xt[:, None, :] - xt[None, :, :]
            d2 = jnp.sum(diff * diff, -1)
            pos = d2 > 0
            r = jnp.sqrt(jnp.where(pos, d2, 1.0))
            mval = jnp.where(pos, (1.0 + SQRT5 * r + 5.0 / 3.0 * d2)
                             * jnp.exp(-SQRT5 * r), 1.0)
        mo = mask[:, None] * mask[None, :]
        n = x.shape[0]
        k = sf * mval * mo + (noise + JITTER) * jnp.eye(n) \
            + jnp.diag(1.0 - mask)
        chol = jnp.linalg.cholesky(k)
        a = jax.scipy.linalg.solve_triangular(chol, y, lower=True)
        return 0.5 * a @ a + jnp.sum(jnp.log(jnp.diagonal(chol)))

    grad = jax.grad(loss)

    def body(carry, i):
        p, m, v = carry
        g = grad(p)
        m = tuple(0.9 * a + 0.1 * b for a, b in zip(m, g))
        v = tuple(0.999 * a + 0.001 * b * b for a, b in zip(v, g))
        t = i + 1.0
        c1, c2 = (1 - 0.9 ** t, 1 - 0.999 ** t) if bias else (1.0, 1.0)
        p = tuple(jnp.clip(pp - lr * (mh / c1) / (jnp.sqrt(vh / c2) + 1e-8),
                           -3.0, 3.0)
                  for pp, mh, vh in zip(p, m, v))
        return (p, m, v), None

    p0 = (init_ls, init_sf)
    z = (jnp.zeros_like(init_ls), jnp.zeros_like(init_sf))
    (p, _, _), _ = jax.lax.scan(body, (p0, z, z),
                                jnp.arange(steps, dtype=init_sf.dtype))
    return p


def fit_lanes(xs: Sequence[np.ndarray], ys: Sequence[np.ndarray],
              init_ls: np.ndarray, init_sf: np.ndarray, *, steps: int,
              noise: float, lr: float, n_pad: int = 0,
              variant: Variant = EXACT) -> Tuple[np.ndarray, np.ndarray]:
    """Fit many GPs at once (one lane each, padded with masked rows that
    carry a unit diagonal, which leaves each lane's NLML gradient that
    of its own observations). -> (log_ls (L, d), log_sf (L,))."""
    import jax
    if variant.fit == "init":
        return (np.asarray(init_ls, np.float64).copy(),
                np.asarray(init_sf, np.float64).copy())
    dt = variant.dtype
    n_pad = max([n_pad] + [len(y) for y in ys])
    d = xs[0].shape[1]
    xp = np.zeros((len(xs), n_pad, d), dt)
    yp = np.zeros((len(xs), n_pad), dt)
    mp = np.zeros((len(xs), n_pad), dt)
    for i, (x, y) in enumerate(zip(xs, ys)):
        xp[i, :len(y)] = x
        yp[i, :len(y)] = standardise(y)[0]
        mp[i, :len(y)] = 1.0
    with jax.enable_x64(dt == np.float64), \
            jax.default_device(jax.devices("cpu")[0]):
        ls, sf = _fit_jit(xp, yp, mp, np.asarray(init_ls, dt),
                          np.asarray(init_sf, dt), steps=steps,
                          noise=float(noise), lr=float(lr),
                          passes=variant.passes,
                          bias=variant.fit != "adam_nobias")
        return np.asarray(ls, np.float64), np.asarray(sf, np.float64)


def _fit_vmapped(x, y, mask, init_ls, init_sf, **kw):
    import jax
    return jax.vmap(partial(_adam_fit, **kw))(x, y, mask, init_ls, init_sf)


_FIT = {}


def _fit_jit(*args, **kw):
    import jax
    key = tuple(sorted(kw.items()))
    fn = _FIT.get(key)
    if fn is None:
        fn = _FIT[key] = jax.jit(partial(_fit_vmapped, **kw))
    return fn(*args)


# -- support selection (Algorithm 1) --------------------------------------------

def _zscore(rows: np.ndarray) -> np.ndarray:
    """Rows centred and scaled so that a dot product over the row
    length is the Pearson correlation (constant rows stay zero)."""
    c = rows - rows.mean(axis=1, keepdims=True)
    sd = np.sqrt(np.mean(c * c, axis=1, keepdims=True))
    return np.where(sd > 1e-12, c / np.where(sd > 1e-12, sd, 1.0), 0.0)


def select_support(target_runs, candidates: Dict[str, List], k: int,
                   default_score: float = 0.5) -> List[str]:
    """Algorithm 1. ``target_runs`` and each candidate's runs are
    (config, metrics matrix) pairs; returns the ``k`` best workloads."""
    ids = list(candidates)
    runs = [(w, c, m) for w in ids for c, m in candidates[w]]
    seg = np.array([ids.index(w) for w, _, _ in runs])
    b = _zscore(np.stack([np.asarray(m, np.float64).reshape(-1)
                          for _, _, m in runs]))
    a = _zscore(np.stack([np.asarray(m, np.float64).reshape(-1)
                          for _, m in target_runs]))
    sim = (a @ b.T / a.shape[1] + 1.0) / 2.0
    same = np.array([[tc["machine_type"] == c["machine_type"]
                      for _, c, _ in runs] for tc, _ in target_runs])
    lt = np.log2([max(tc["node_count"], 1) for tc, _ in target_runs])
    lc = np.log2([max(c["node_count"], 1) for _, c, _ in runs])
    w = np.where(same, 2.0 ** -np.abs(lt[:, None] - lc[None, :]), 0.0)
    num = np.bincount(seg, (w * sim).sum(0), len(ids))
    den = np.bincount(seg, w.sum(0), len(ids))
    score = np.where(den > 0, num / np.where(den > 0, den, 1.0),
                     default_score)
    order = sorted(range(len(ids)), key=lambda i: -score[i])
    return [ids[i] for i in order[:k]]


# -- RGPE --------------------------------------------------------------------------

def derive_key(seed: int, purpose: int, it: int, index: int):
    import jax
    k = jax.random.PRNGKey(seed)
    for v in (purpose, it, index):
        k = jax.random.fold_in(k, v)
    return k


def normals(key, shape) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(jax.random.normal(key, shape, jnp.float32),
                          np.float64)


def split(key, n: int):
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        return list(jax.random.split(key, n))


def ranking_losses(samples: np.ndarray, y: np.ndarray) -> np.ndarray:
    """(S, n) draws against observed y -> (S,) misranked ordered pairs."""
    pl = samples[:, :, None] < samples[:, None, :]
    yl = (y[:, None] < y[None, :])[None]
    return np.sum(pl ^ yl, axis=(1, 2)).astype(np.float64)


def rgpe_weights(bases: Sequence[GP64], target: GP64, key,
                 n_samples: int, uniform: bool = False) -> np.ndarray:
    m = len(bases)
    n = len(target.y)
    if n < 2 or uniform:
        return np.full(m + 1, 1.0 / (m + 1))
    keys = split(key, m + 1)
    losses = []
    for i, gp in enumerate(bases):
        mu, var = gp.posterior(target.x)
        s = mu[None] + normals(keys[i], (n_samples, n)) * np.sqrt(var)[None]
        losses.append(ranking_losses(s, target.y))
    mu, var = target.loo()
    s = mu[None] + normals(keys[m], (n_samples, n)) * np.sqrt(var)[None]
    losses.append(ranking_losses(s, target.y))
    lm = np.stack(losses)
    tar_pct = np.percentile(lm[-1], 95.0)
    diluted = np.median(lm, axis=1) > tar_pct
    diluted[-1] = False
    lm = np.where(diluted[:, None], np.inf, lm)
    is_min = (lm == lm.min(axis=0, keepdims=True)).astype(np.float64)
    w = np.mean(is_min / is_min.sum(axis=0, keepdims=True), axis=1)
    return w / w.sum()


# -- acquisitions -----------------------------------------------------------------

def _phi(z):
    return np.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)


def _Phi(z):
    from scipy.special import erf
    return 0.5 * (1.0 + erf(z / math.sqrt(2.0)))


def constrained_ei(mu, var, best, constraints) -> np.ndarray:
    """EI of minimising, times each constraint's P(measure <= bound);
    ``constraints``: (mu, var, standardised bound)."""
    sigma = np.sqrt(np.maximum(var, VAR_FLOOR))
    z = (best - mu) / sigma
    acq = np.maximum(sigma * (z * _Phi(z) + _phi(z)), 0.0)
    for mu_c, var_c, ub in constraints:
        acq = acq * _Phi((ub - mu_c) / np.sqrt(np.maximum(var_c, VAR_FLOOR)))
    return acq


def pareto_front(points: np.ndarray) -> np.ndarray:
    """Non-dominated distinct points (minimisation)."""
    keep = []
    for i, p in enumerate(points):
        dominated = np.any(np.all(points <= p, axis=1)
                           & np.any(points < p, axis=1))
        duplicate = bool(np.any(np.all(points[:i] == p, axis=1)))
        if not dominated and not duplicate:
            keep.append(i)
    return points[keep]


def hvi_2d(front: np.ndarray, ref: np.ndarray, p: np.ndarray) -> np.ndarray:
    """Hypervolume each point of ``p`` (..., 2) adds to a 2-D ``front``
    below ``ref``: the integral over f1 of the gap between p's f2 and the
    front's staircase."""
    f = front[np.all(front <= ref, axis=1)]
    f = f[np.argsort(f[:, 0])]
    # staircase: below f[0] the region is open up to ref2; between f[i]
    # and f[i+1] (or ref1) it is open below f[i]'s f2
    edges = np.concatenate([[-np.inf], f[:, 0], [ref[0]]])
    heights = np.concatenate([[ref[1]], f[:, 1]])
    lo = np.maximum(edges[:-1], p[..., :1])
    width = np.clip(np.minimum(edges[1:], ref[0]) - lo, 0.0, None)
    height = np.clip(np.minimum(heights, ref[1]) - p[..., 1:2], 0.0, None)
    return np.sum(width * height, axis=-1)


def hv_nd(points: np.ndarray, ref: np.ndarray) -> float:
    """Dominated hypervolume by recursive slicing (any dimension)."""
    ref = np.asarray(ref, np.float64)
    d = ref.shape[0]
    pts = np.asarray(points, np.float64).reshape(-1, d)
    pts = pts[np.all(pts <= ref, axis=1)]
    if len(pts) == 0:
        return 0.0
    if d == 1:
        return float(ref[0] - pts.min())
    hv = 0.0
    zs = np.unique(pts[:, -1])
    for i, z in enumerate(zs):
        z_hi = zs[i + 1] if i + 1 < len(zs) else ref[-1]
        if z_hi > z:
            hv += (z_hi - z) * hv_nd(pts[pts[:, -1] <= z][:, :-1], ref[:-1])
    return float(hv)


def mc_ehvi(samples: Sequence[np.ndarray], observed: np.ndarray,
            ref: np.ndarray) -> np.ndarray:
    """MC EHVI: ``samples`` one (S, q) raw-scale draw array per
    objective -> (q,) mean hypervolume improvement."""
    ref = np.asarray(ref, np.float64)
    front = pareto_front(np.asarray(observed, np.float64))
    p = np.stack(samples, axis=-1)                       # (S, q, D)
    if len(ref) == 2:
        return hvi_2d(front, ref, p).mean(axis=0)
    hv0 = hv_nd(front, ref)
    s, q, _ = p.shape
    return np.array([np.mean([max(hv_nd(np.vstack([front, p[i, j][None]]),
                                        ref) - hv0, 0.0)
                              for i in range(s)]) for j in range(q)])


# -- one tenant's decisions ---------------------------------------------------------

class Tenant64:
    """The reference's replay of one tenant: its fit chain and the
    acquisition of each decision."""

    def __init__(self, tenant, obs: List[Tuple[Dict, Dict, np.ndarray]],
                 config: Dict, grid: List[Dict], support: "Support",
                 variant: Variant = EXACT):
        self.tenant = tenant
        self.variant = variant
        self.obs = obs
        self.cfg = config
        self.bo = config["bo"]
        self.grid = grid
        self.xgrid = np.stack([encode(c) for c in grid])
        self.support = support
        self.measures = list(tenant.objectives) + list(tenant.constraints)
        self.x = np.stack([encode(c) for c, _, _ in obs])
        self.fits: Dict[Tuple[str, int], Tuple[np.ndarray, float]] = {}

    def y(self, m: str, n: int) -> np.ndarray:
        return np.array([ms[m] for _, ms, _ in self.obs[:n]])

    def gp(self, m: str, n: int) -> GP64:
        ls, sf = self.fits[(m, n)]
        return GP64(self.x[:n], self.y(m, n), ls, sf, self.bo["noise"],
                    self.variant.passes)

    def remaining(self, n: int) -> List[int]:
        seen = {tuple(sorted(c.items())) for c, _, _ in self.obs[:n]}
        return [i for i, c in enumerate(self.grid)
                if tuple(sorted(c.items())) not in seen]

    def posteriors(self, n: int) -> Dict[str, Tuple]:
        """measure -> (mu, var, y_mean, y_std) over the grid: the tenant
        model mixed with its support models by RGPE weights."""
        bo = self.bo
        selected = []
        if self.cfg["method"] == "karasu":
            selected = select_support(
                [(c, mt) for c, _, mt in self.obs[:n]],
                self.support.candidates, bo["n_support"])
        out = {}
        for mi, m in enumerate(self.measures):
            tgt = self.gp(m, n)
            mu, var = tgt.posterior(self.xgrid)
            bases = [self.support.gp(w, m) for w in selected]
            if bases:
                key = derive_key(self.tenant.seed, PURPOSE_RGPE, n, mi)
                w = rgpe_weights(bases, tgt, key, bo["rgpe_samples"],
                                 self.variant.rgpe == "uniform")
                mus, vs = zip(*(b.posterior(self.xgrid) for b in bases))
                mu = np.sum(w[:-1, None] * np.stack(mus), 0) + w[-1] * mu
                var = np.maximum(np.sum(w[:-1, None] ** 2 * np.stack(vs), 0)
                                 + w[-1] ** 2 * var, 1e-10)
            out[m] = (mu, var, tgt.mean, tgt.std)
        return out

    def acquisition(self, n: int) -> Tuple[List[int], np.ndarray]:
        """(remaining configurations, acquisition over them) of the
        decision made on the first ``n`` observations."""
        post = self.posteriors(n)
        rem = self.remaining(n)
        idx = np.asarray(rem)
        cons = self.tenant.constraints
        obs = self.obs[:n]
        feasible = [ms for _, ms, _ in obs
                    if all(ms[c] <= ub for c, ub in cons.items())]
        pof = np.ones(len(rem))
        for c, ub in cons.items():
            mu_c, var_c, ym, ys = post[c]
            pof = pof * _Phi(((ub - ym) / ys - mu_c[idx])
                             / np.sqrt(np.maximum(var_c[idx], VAR_FLOOR)))
        objs = self.tenant.objectives
        if len(objs) == 1:
            o = objs[0]
            vals = [ms[o] for ms in feasible] or [ms[o] for _, ms, _ in obs]
            mu, var, ym, ys = post[o]
            best = (min(vals) - ym) / ys
            return rem, constrained_ei(mu[idx], var[idx], best, []) * pof
        pts = np.array([[ms[o] for o in objs]
                        for ms in (feasible or [ms for _, ms, _ in obs])])
        ref = pts.max(axis=0) * 1.1 + 1e-9
        samples = []
        for oi, o in enumerate(objs):
            mu, var, ym, ys = post[o]
            eps = normals(derive_key(self.tenant.seed, PURPOSE_EHVI, n, oi),
                          (self.tenant.n_mc, len(rem)))
            samples.append((mu[idx][None] + eps * np.sqrt(var[idx])[None])
                           * ys + ym)
        return rem, mc_ehvi(samples, pts, ref) * pof


class Support:
    """The shared repository's support models, fitted by the reference."""

    def __init__(self, runs, config: Dict, variant: Variant = EXACT):
        self.config = config
        self.variant = variant
        self.candidates: Dict[str, List] = {}
        self.data: Dict[str, List] = {}
        for anon, c, ms, mt in runs:
            self.candidates.setdefault(anon, []).append((c, mt))
            self.data.setdefault(anon, []).append((c, ms))
        self.fits: Dict[Tuple[str, str], Tuple[np.ndarray, float]] = {}

    def xy(self, wid: str, m: str):
        rows = self.data[wid]
        return (np.stack([encode(c) for c, _ in rows]),
                np.array([ms[m] for _, ms in rows]))

    def fit(self, keys: Sequence[Tuple[str, str]]) -> None:
        todo = [k for k in keys if k not in self.fits]
        if not todo:
            return
        g = self.config["gp_fit"]
        xs, ys = zip(*(self.xy(w, m) for w, m in todo))
        d = xs[0].shape[1]
        ls, sf = fit_lanes(xs, ys, np.zeros((len(todo), d)),
                           np.zeros(len(todo)), steps=g["cold_steps"],
                           noise=self.config["bo"]["noise"], lr=g["lr"],
                           variant=self.variant)
        for i, k in enumerate(todo):
            self.fits[k] = (ls[i], float(sf[i]))

    def gp(self, wid: str, m: str) -> GP64:
        self.fit([(wid, m)])
        x, y = self.xy(wid, m)
        ls, sf = self.fits[(wid, m)]
        return GP64(x, y, ls, sf, self.config["bo"]["noise"],
                    self.variant.passes)


def fit_chains(tenants: Sequence[Tenant64], upto: Dict[int, int]) -> None:
    """Replay every tenant model's fit chain up to ``upto[index]``
    observations: the first fit cold from zero, each later one warm from
    the fit before it. All lanes of one chain position fit together."""
    g = tenants[0].cfg["gp_fit"]
    noise = tenants[0].bo["noise"]
    lanes = [(t, m) for t in tenants for m in t.measures]
    if not lanes:
        return
    d = tenants[0].x.shape[1]
    ls = np.zeros((len(lanes), d))
    sf = np.zeros(len(lanes))
    top = max(upto.values())
    for n in range(1, top + 1):
        # every lane fits at each chain position (one program per rung);
        # a lane past its tenant's last needed fit is computed and dropped
        cnt = [min(n, upto[t.tenant.index]) for t, _ in lanes]
        xs = [t.x[:c] for (t, _), c in zip(lanes, cnt)]
        ys = [t.y(m, c) for (t, m), c in zip(lanes, cnt)]
        steps = g["cold_steps"] if n == 1 else g["warm_steps"]
        nl, ns = fit_lanes(xs, ys, ls, sf, steps=steps, noise=noise,
                           lr=g["lr"], n_pad=top,
                           variant=tenants[0].variant)
        for i, ((t, m), c) in enumerate(zip(lanes, cnt)):
            if c == n:
                ls[i], sf[i] = nl[i], ns[i]
                t.fits[(m, n)] = (nl[i].copy(), float(ns[i]))

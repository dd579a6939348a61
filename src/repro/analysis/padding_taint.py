"""Rule ``padding-taint``: padded regions cannot reach valid outputs.

One ``LaunchSpec`` per tracked launch family (fit, chol_alpha,
posterior, sample, loo, ehvi, the padded ranking loss, and the fused
Pallas kernels — posterior, EHVI, fit — via their XLA ref twins — the
jaxpr-level dataflow is the kernels' specification,
and the donated / sharded twins jit the SAME bodies, so one spec covers
the family). Each spec carries concrete example arguments exercising
every pad axis the executor can produce, a taint mask marking the FREE
padded regions, and a valid-region mask per output; the differential
interpreter in ``taint`` then proves no free pad value can perturb a
valid-region result.

Free vs contract-pinned pads: a free region may hold ANYTHING (padded
observation rows, padded alpha/y entries, padded grid columns, padded
draw columns, entire throwaway lanes) — the launch must mask it out.
A pinned region's VALUE is part of the launch contract (the padded
Cholesky block's unit diagonal / zero off-blocks, the +inf EHVI padding
boxes): launches legitimately rely on those values, so they are not
taint sources here — instead ``chol_alpha``'s spec proves the pinned
Cholesky structure is itself never contaminated by free pads, and the
executors construct the +inf paddings from constants every step.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Finding
from .taint import taint_trace


@dataclasses.dataclass
class LaunchSpec:
    """A launch family's static-analysis fixture."""
    name: str                  # tracked launch family name
    fn: Callable               # unjitted body, static kwargs bound
    args: Tuple                # concrete example arguments
    taints: Tuple              # bool mask per arg: free padded regions
    valid_outs: Tuple          # bool mask per FLAT output: valid region
    arg_names: Tuple[str, ...] = ()   # for weak-type reporting
    twins: Tuple = ()          # jitted (plain, donated) pair, if any


def _zeros_like_masks(args) -> List[np.ndarray]:
    return [np.zeros(np.shape(a), bool) for a in args]


def _stack_fixture():
    """A 4-lane stacked-GP fixture: lanes 0/1 real (5 and 3 valid
    observations of 8 padded), lanes 2/3 throwaway copies of lane 0 —
    exactly what ``_stack_parts`` + ``_pad_lanes`` assemble."""
    from repro.core import gp as gp_mod
    rng = np.random.default_rng(0)
    m_valid, m_pad, n_pad, d = 2, 4, 8, 2
    ns = (5, 3)
    x = np.zeros((m_pad, n_pad, d), np.float32)
    y = np.zeros((m_pad, n_pad), np.float32)
    mask = np.zeros((m_pad, n_pad), np.float32)
    for i, n in enumerate(ns):
        x[i, :n] = rng.uniform(0.0, 1.0, (n, d))
        y[i, :n] = rng.normal(0.0, 1.0, (n,))
        mask[i, :n] = 1.0
    x[m_valid:] = x[0]
    y[m_valid:] = y[0]
    mask[m_valid:] = mask[0]
    log_ls = rng.normal(0.0, 0.3, (m_pad, d)).astype(np.float32)
    log_sf = rng.normal(0.0, 0.3, (m_pad,)).astype(np.float32)
    log_ls[m_valid:] = log_ls[0]
    log_sf[m_valid:] = log_sf[0]
    chol, alpha = gp_mod._batched_chol_alpha(log_ls, log_sf, x, y, mask,
                                             0.1)
    chol = np.asarray(chol)
    alpha = np.asarray(alpha)

    def obs_pad_mask(shape_tail=()):
        """True at padded observation rows of valid lanes and on every
        throwaway lane."""
        t = np.zeros((m_pad, n_pad) + shape_tail, bool)
        for i, n in enumerate(ns):
            t[i, n:] = True
        t[m_valid:] = True
        return t

    def lane_pad_mask(shape):
        t = np.zeros(shape, bool)
        t[m_valid:] = True
        return t

    return dict(rng=rng, m_valid=m_valid, m_pad=m_pad, n_pad=n_pad, d=d,
                ns=ns, x=x, y=y, mask=mask, log_ls=log_ls,
                log_sf=log_sf, chol=chol, alpha=alpha,
                obs_pad_mask=obs_pad_mask, lane_pad_mask=lane_pad_mask)


def _gp_specs() -> List[LaunchSpec]:
    from repro.core import gp as gp_mod
    fx = _stack_fixture()
    rng = fx["rng"]
    m_valid, m_pad, n_pad, d = (fx["m_valid"], fx["m_pad"], fx["n_pad"],
                                fx["d"])
    lane = fx["lane_pad_mask"]
    obs = fx["obs_pad_mask"]
    valid_lanes_mask = lambda shape: ~lane(shape)
    specs = []

    # --- fit: (x, y, mask, lr) -> {"ls": (m, d), "sf": (m,)} ---------
    fit_body = gp_mod._fit_batched.__wrapped__
    specs.append(LaunchSpec(
        name="fit",
        fn=lambda x, y, mask, lr: fit_body(x, y, mask, steps=2,
                                           noise=0.1, lr=lr),
        args=(fx["x"], fx["y"], fx["mask"], 0.05),
        taints=(obs((d,)), obs(), lane((m_pad, n_pad)),
                np.zeros((), bool)),
        valid_outs=(valid_lanes_mask((m_pad, d)),        # ls
                    valid_lanes_mask((m_pad,))),         # sf
        arg_names=("x", "y", "mask", "lr"),
        twins=(gp_mod._fit_batched, None)))

    # --- chol_alpha: the pinned-pad producer. Its whole valid-lane
    # Cholesky output (INCLUDING the unit-diagonal pad block downstream
    # launches rely on) must be untouchable by free pads; alpha's
    # padded entries mirror y's padded entries, so only its valid
    # entries are claimed.
    ca_valid_chol = valid_lanes_mask((m_pad, n_pad, n_pad))
    ca_valid_alpha = np.zeros((m_pad, n_pad), bool)
    for i, n in enumerate(fx["ns"]):
        ca_valid_alpha[i, :n] = True
    specs.append(LaunchSpec(
        name="chol_alpha",
        fn=partial(gp_mod._batched_chol_alpha.__wrapped__, noise=0.1),
        args=(fx["log_ls"], fx["log_sf"], fx["x"], fx["y"], fx["mask"]),
        taints=(lane((m_pad, d)), lane((m_pad,)), obs((d,)), obs(),
                lane((m_pad, n_pad))),
        valid_outs=(ca_valid_chol, ca_valid_alpha),
        arg_names=("log_ls", "log_sf", "x", "y", "mask"),
        twins=(gp_mod._batched_chol_alpha, None)))

    # --- posterior: q exact (the service always queries the full grid)
    q = 4
    xq = rng.uniform(0.0, 1.0, (m_pad, q, d)).astype(np.float32)
    xq[m_valid:] = xq[0]
    alpha_taint = obs()          # padded alpha entries + pad lanes free
    post_args = (fx["log_ls"], fx["log_sf"], fx["x"], fx["mask"],
                 fx["chol"], fx["alpha"], xq)
    post_taints = (lane((m_pad, d)), lane((m_pad,)), obs((d,)),
                   lane((m_pad, n_pad)),           # mask values pinned
                   lane((m_pad, n_pad, n_pad)),    # chol pads pinned
                   alpha_taint, lane((m_pad, q, d)))
    post_names = ("log_ls", "log_sf", "x", "mask", "chol", "alpha",
                  "xq")
    specs.append(LaunchSpec(
        name="posterior",
        fn=partial(gp_mod._batched_posterior.__wrapped__, impl="xla"),
        args=post_args, taints=post_taints,
        valid_outs=(valid_lanes_mask((m_pad, q)),
                    valid_lanes_mask((m_pad, q))),
        arg_names=post_names,
        twins=(gp_mod._batched_posterior,
               gp_mod._batched_posterior_donated)))

    # --- sample: adds the padded grid axis and the eps draw tensor ---
    s, q_s, q_pad = 3, 5, 8
    xq_s = np.zeros((m_pad, q_pad, d), np.float32)
    xq_s[:, :q_s] = rng.uniform(0.0, 1.0, (m_pad, q_s, d))
    xq_s[:, q_s:] = xq_s[:, q_s - 1:q_s]     # edge-padded grid rows
    xq_s[m_valid:] = xq_s[0]
    eps = np.zeros((m_pad, s, q_pad), np.float32)
    eps[:, :, :q_s] = rng.normal(0.0, 1.0, (m_pad, s, q_s))
    eps[m_valid:] = eps[0]
    xq_taint = np.zeros((m_pad, q_pad, d), bool)
    xq_taint[:, q_s:] = True          # edge-padded grid rows are free
    xq_taint[m_valid:] = True
    eps_taint = np.zeros((m_pad, s, q_pad), bool)
    eps_taint[:, :, q_s:] = True      # zero-padded draw columns free
    eps_taint[m_valid:] = True
    sample_valid = np.zeros((m_pad, s, q_pad), bool)
    sample_valid[:m_valid, :, :q_s] = True
    specs.append(LaunchSpec(
        name="sample",
        fn=partial(gp_mod._batched_sample_launch.__wrapped__,
                   impl="xla"),
        args=(fx["log_ls"], fx["log_sf"], fx["x"], fx["mask"],
              fx["chol"], fx["alpha"], xq_s, eps),
        taints=(lane((m_pad, d)), lane((m_pad,)), obs((d,)),
                lane((m_pad, n_pad)), lane((m_pad, n_pad, n_pad)),
                alpha_taint, xq_taint, eps_taint),
        valid_outs=(sample_valid,),
        arg_names=post_names + ("eps",),
        twins=(gp_mod._batched_sample_launch,
               gp_mod._batched_sample_launch_donated)))

    # --- loo: block-padded per-target chol/alpha/y + padded draws ----
    n_loo, l_valid, l_pad, s_loo = int(fx["ns"][0]), 2, 4, 3
    p = n_pad - n_loo
    chol_l = np.zeros((l_pad, n_pad, n_pad), np.float32)
    alpha_l = np.zeros((l_pad, n_pad), np.float32)
    y_l = np.zeros((l_pad, n_pad), np.float32)
    bump = np.diag(np.concatenate([np.zeros(n_loo), np.ones(p)]))
    for j in range(l_valid):
        # lane 0's valid block reused per target: structure is what the
        # rule exercises, not the particular factor
        chol_l[j, :n_loo, :n_loo] = fx["chol"][0][:n_loo, :n_loo]
        chol_l[j] += bump.astype(np.float32)
        alpha_l[j, :n_loo] = fx["alpha"][0][:n_loo]
        y_l[j, :n_loo] = fx["y"][0][:n_loo]
    chol_l[l_valid:] = chol_l[0]
    alpha_l[l_valid:] = alpha_l[0]
    y_l[l_valid:] = y_l[0]
    eps_l = np.zeros((l_pad, s_loo, n_pad), np.float32)
    eps_l[:, :, :n_loo] = rng.normal(0.0, 1.0, (l_pad, s_loo, n_loo))

    def loo_pad(shape_tail=()):
        t = np.zeros((l_pad, n_pad) + shape_tail, bool)
        t[:, n_loo:] = True
        t[l_valid:] = True
        return t

    lane_l = np.zeros((l_pad, n_pad, n_pad), bool)
    lane_l[l_valid:] = True
    eps_l_taint = np.zeros((l_pad, s_loo, n_pad), bool)
    eps_l_taint[:, :, n_loo:] = True
    eps_l_taint[l_valid:] = True
    loo_valid = np.zeros((l_pad, s_loo, n_pad), bool)
    loo_valid[:l_valid, :, :n_loo] = True
    specs.append(LaunchSpec(
        name="loo",
        fn=gp_mod._batched_loo_launch.__wrapped__,
        args=(chol_l, alpha_l, y_l, eps_l),
        taints=(lane_l,          # chol pads pinned, only lanes free
                loo_pad(), loo_pad(), eps_l_taint),
        valid_outs=(loo_valid,),
        arg_names=("chol", "alpha", "y", "eps"),
        twins=(gp_mod._batched_loo_launch,
               gp_mod._batched_loo_launch_donated)))
    return specs


def _ehvi_fixture():
    """A 4-lane EHVI bucket (2 real lanes), 2 objectives, 5 of 8
    candidates valid, front boxes padded with the +inf pinned boxes."""
    from repro.core.acquisition import nondominated_boxes, pareto_front
    rng = np.random.default_rng(1)
    l_valid, l_pad, n_obj, s, q_v, q_pad = 2, 4, 2, 4, 5, 8
    observed = rng.normal(0.0, 1.0, (3, n_obj))
    ref = np.full((n_obj,), 3.0)
    lo, hi = nondominated_boxes(pareto_front(observed), ref)
    k = lo.shape[0]
    k_pad = 1 << (k - 1).bit_length()
    los = np.full((l_pad, k_pad, n_obj), np.inf, np.float32)
    his = np.full((l_pad, k_pad, n_obj), np.inf, np.float32)
    los[:, :k] = lo
    his[:, :k] = hi
    refs = np.broadcast_to(ref.astype(np.float32),
                           (l_pad, n_obj)).copy()
    return dict(rng=rng, l_valid=l_valid, l_pad=l_pad, n_obj=n_obj,
                s=s, q_v=q_v, q_pad=q_pad, los=los, his=his, refs=refs)


def _ehvi_specs() -> List[LaunchSpec]:
    from repro.core import acquisition as acq
    from repro.kernels.fused_ehvi import ops as fe_ops
    fx = _ehvi_fixture()
    rng = fx["rng"]
    l_valid, l_pad, n_obj, s, q_v, q_pad = (
        fx["l_valid"], fx["l_pad"], fx["n_obj"], fx["s"], fx["q_v"],
        fx["q_pad"])

    def lane(shape):
        t = np.zeros(shape, bool)
        t[l_valid:] = True
        return t

    def cols(shape, axis=-1):
        """Free padded candidate columns (last axis) + pad lanes."""
        t = np.zeros(shape, bool)
        t[..., q_v:] = True
        t[l_valid:] = True
        return t

    valid_rows = np.zeros((l_pad, q_pad), bool)
    valid_rows[:l_valid, :q_v] = True

    # --- vmapped ehvi: (los, his, refs, ps) -> (L, q) ----------------
    ps = rng.normal(0.0, 1.0,
                    (l_pad, n_obj, s, q_pad)).astype(np.float32)
    ps[..., q_v:] = np.inf          # executor pads candidates at +inf
    specs = [LaunchSpec(
        name="ehvi",
        fn=acq._ehvi_box_eval,
        args=(fx["los"], fx["his"], fx["refs"], ps),
        taints=(lane(fx["los"].shape),    # +inf boxes pinned
                lane(fx["his"].shape),
                lane(fx["refs"].shape),
                cols(ps.shape)),
        valid_outs=(valid_rows,),
        arg_names=("los", "his", "refs", "ps"),
        twins=(acq._ehvi_box_launch, acq._ehvi_box_launch_donated))]

    # --- fused ehvi (ref twin): draw affine fused in ------------------
    mu = np.zeros((l_pad, n_obj, q_pad), np.float32)
    mu[:, :, :q_v] = rng.normal(0.0, 1.0, (l_pad, n_obj, q_v))
    mu[:, :, q_v:] = np.inf
    var = np.zeros((l_pad, n_obj, q_pad), np.float32)
    var[:, :, :q_v] = rng.uniform(0.1, 1.0, (l_pad, n_obj, q_v))
    y_mean = rng.normal(0.0, 1.0, (l_pad, n_obj)).astype(np.float32)
    y_std = rng.uniform(0.5, 1.5, (l_pad, n_obj)).astype(np.float32)
    eps = np.zeros((l_pad, n_obj, s, q_pad), np.float32)
    eps[..., :q_v] = rng.normal(0.0, 1.0, (l_pad, n_obj, s, q_v))
    specs.append(LaunchSpec(
        name="fused_ehvi",
        fn=fe_ops.ref_twin(),
        args=(fx["los"], fx["his"], fx["refs"], mu, var, y_mean, y_std,
              eps),
        taints=(lane(fx["los"].shape), lane(fx["his"].shape),
                lane(fx["refs"].shape), cols(mu.shape),
                cols(var.shape), lane(y_mean.shape),
                lane(y_std.shape), cols(eps.shape)),
        valid_outs=(valid_rows,),
        arg_names=("los", "his", "refs", "mu", "var", "y_mean",
                   "y_std", "eps"),
        twins=(fe_ops._fused_ehvi_launch,
               fe_ops._fused_ehvi_launch_donated)))
    return specs


def _fused_posterior_spec() -> LaunchSpec:
    from repro.core import gp as gp_mod
    from repro.kernels.fused_posterior import ops as fp_ops
    fx = _stack_fixture()
    rng = fx["rng"]
    m_valid, m_pad, n_pad, d = (fx["m_valid"], fx["m_pad"], fx["n_pad"],
                                fx["d"])
    q = 4
    xq = rng.uniform(0.0, 1.0, (m_pad, q, d)).astype(np.float32)
    xq[m_valid:] = xq[0]
    best = rng.normal(0.0, 1.0, (m_pad,)).astype(np.float32)
    best[m_valid:] = best[0]
    lane = fx["lane_pad_mask"]
    obs = fx["obs_pad_mask"]
    valid = np.zeros((m_pad, q), bool)
    valid[:m_valid] = True
    return LaunchSpec(
        name="fused_posterior",
        fn=fp_ops.ref_twin(),
        args=(fx["log_ls"], fx["log_sf"], fx["x"], fx["mask"],
              fx["chol"], fx["alpha"], xq, best),
        taints=(lane((m_pad, d)), lane((m_pad,)), obs((d,)),
                lane((m_pad, n_pad)), lane((m_pad, n_pad, n_pad)),
                obs(), lane((m_pad, q, d)), lane((m_pad,))),
        valid_outs=(valid, valid, valid),
        arg_names=("log_ls", "log_sf", "x", "mask", "chol", "alpha",
                   "xq", "best"),
        twins=(fp_ops._fused_posterior_launch,
               fp_ops._fused_posterior_launch_donated))


def _fused_fit_spec() -> LaunchSpec:
    """The fused fit leg: warm-start rows ride the lane axis, padded
    observation rows must have exactly zero gradient (the masked-NLML
    contract in ``kernels/fused_fit/ref.py``), and the emitted Cholesky
    must keep its pinned pad block untouchable — the posterior legs
    consume it directly."""
    from repro.kernels.fused_fit import ops as ff_ops
    fx = _stack_fixture()
    m_valid, m_pad, n_pad, d = (fx["m_valid"], fx["m_pad"], fx["n_pad"],
                                fx["d"])
    lane = fx["lane_pad_mask"]
    obs = fx["obs_pad_mask"]
    valid_alpha = np.zeros((m_pad, n_pad), bool)
    for i, n in enumerate(fx["ns"]):
        valid_alpha[i, :n] = True
    return LaunchSpec(
        name="fused_fit",
        fn=partial(ff_ops.ref_twin(), steps=2, noise=0.1, lr=0.05),
        args=(fx["x"], fx["y"], fx["mask"], fx["log_ls"], fx["log_sf"]),
        taints=(obs((d,)), obs(), lane((m_pad, n_pad)),  # mask pinned
                lane((m_pad, d)), lane((m_pad,))),
        valid_outs=(~lane((m_pad, d)),                   # log_ls
                    ~lane((m_pad,)),                     # log_sf
                    ~lane((m_pad, n_pad, n_pad)),        # chol, pad
                    valid_alpha),                        # block included
        arg_names=("x", "y", "mask", "init_ls", "init_sf"),
        twins=(ff_ops._fused_fit_launch, ff_ops._fused_fit_launch_donated))


def _ranking_loss_spec() -> LaunchSpec:
    """The padded RGPE scoring launch: pad rows (n_valid = 0) and each
    row's pad columns are free; the per-row validity mask must fence
    them out of every real row's misrank count."""
    from repro.kernels.ranking_loss import ops as rl_ops
    from repro.kernels.ranking_loss.ref import ranking_loss_padded_ref
    rng = np.random.default_rng(2)
    r_valid, r_pad, n_pad = 3, 4, 8
    nvs = (5, 5, 3)
    preds = np.zeros((r_pad, n_pad), np.float32)
    ys = np.zeros((r_pad, n_pad), np.float32)
    nv = np.zeros((r_pad,), np.int32)
    for i, n in enumerate(nvs):
        preds[i, :n] = rng.normal(0.0, 1.0, (n,))
        ys[i, :n] = rng.normal(0.0, 1.0, (n,))
        nv[i] = n
    taint = np.zeros((r_pad, n_pad), bool)
    for i, n in enumerate(nvs):
        taint[i, n:] = True
    taint[r_valid:] = True
    valid = np.zeros((r_pad,), bool)
    valid[:r_valid] = True
    return LaunchSpec(
        name="ranking_loss",
        fn=ranking_loss_padded_ref,
        args=(preds, ys, nv),
        taints=(taint, taint.copy(), np.zeros((r_pad,), bool)),  # nv pinned
        valid_outs=(valid,),
        arg_names=("preds", "ys", "n_valid"),
        twins=(rl_ops._ranking_loss_launch,
               rl_ops._ranking_loss_launch_donated))


_SPECS: Optional[List[LaunchSpec]] = None


def launch_specs(refresh: bool = False) -> List[LaunchSpec]:
    """The analysis fixtures for every tracked launch family, built
    once per process (fixture construction runs a real ``chol_alpha``
    launch)."""
    global _SPECS
    if _SPECS is None or refresh:
        _SPECS = (_gp_specs() + _ehvi_specs()
                  + [_fused_posterior_spec(), _fused_fit_spec(),
                     _ranking_loss_spec()])
    return _SPECS


def check_padding_taint(
        specs: Optional[Sequence[LaunchSpec]] = None) -> List[Finding]:
    """Run the taint interpreter over every spec; a finding is a free
    padded source reaching a valid-region output position."""
    specs = launch_specs() if specs is None else specs
    out: List[Finding] = []
    for spec in specs:
        taints = [np.zeros(np.shape(a), bool) if t is False else t
                  for a, t in zip(spec.args, spec.taints)]
        res = taint_trace(spec.fn, spec.args, taints)
        if len(res.out_taints) != len(spec.valid_outs):
            out.append(Finding(
                "padding-taint", "error", spec.name, "<outputs>",
                f"spec expects {len(spec.valid_outs)} outputs, launch "
                f"produced {len(res.out_taints)}"))
            continue
        for j, (taint, valid) in enumerate(zip(res.out_taints,
                                               spec.valid_outs)):
            leak = taint & valid
            if leak.any():
                path = " -> ".join(res.out_paths[j]) or "<direct>"
                out.append(Finding(
                    "padding-taint", "error", spec.name, path,
                    f"free padded region reaches {int(leak.sum())} "
                    f"valid position(s) of output {j}"))
    return out

"""End-to-end training driver.

Runs any registered arch (full or reduced) on one device, with:
  * deterministic restart-safe data pipeline
  * atomic async checkpointing + restore (resume with --resume)
  * straggler monitoring + non-finite-step skipping (TrainSupervisor logic)
  * optional int8 gradient compression with error feedback (--compress-grads)

Example (CPU, ~100M-param reduced model, a few hundred steps):
    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduce \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import base as cfgbase
from repro.data.pipeline import ShardedLoader, TokenTaskConfig
from repro.distributed.fault_tolerance import StragglerPolicy
from repro.models import transformer as T
from repro.optim import adamw, compression


def emit_static_mapping(params, cfg, platform, out_path, max_cout=512,
                        stacked_prefixes=("units", "enc_units"),
                        plan_hints=None, act_log_scale=None, bias=None):
    """Write a schema-v2 `repro.api` mapping artifact for the trained
    model's projection weights: per-layer min-cost static channel split
    (paper Sec. IV baselines) under the named platform's cost model, with
    max-abs weight quant scales so the artifact lowers to an executable
    `ExecutionPlan` (``serve.py --mapping`` per-layer planned execution).

    Layer names are params-pytree paths in flatten order (not network
    order).  Three weight layouts are covered:

      * 2-D ``(C_in, C_out)`` dense matrices -> one layer per weight;
      * 3-D ``(R, C_in, C_out)`` scan-stacked dense matrices (leaves under
        a ``stacked_prefixes`` subtree) -> one layer PER REPEAT, named
        ``path@r`` with that repeat's own max-abs scale, so every scanned
        layer binds and executes as mapped (no silent fp fallbacks);
      * 4-D ``(kh, kw, C_in, C_out)`` HWIO conv kernels -> one layer per
        conv, lowered through the im2col execution path.

    ``plan_hints`` — optional ``{name: (LayerGeometry, searchable)}`` from a
    façade's ``plan()`` — supplies the true cost-model geometry (conv output
    maps, groups) and searchability; grouped/depthwise convs are EMITTED
    with their group count (``"groups"`` on the artifact layer) and lower
    block-diagonally onto the im2col'd kernels — mbv1's own artifact passes
    ``--require-full-coverage``.  Without hints, conv geometry falls back to
    the weight shape alone (ox/oy unknown -> 1, groups unknown -> 1).

    ``act_log_scale``: None (default) leaves activation scales null — the
    executors then quantize activations DYNAMICALLY per call with the
    batch's max-abs, which makes planned outputs depend on batch
    composition.  Pass a float to pin a STATIC activation scale on every
    layer instead — required for the serving engine's per-request
    reproducibility guarantee (`repro.serving`: a request's tokens must not
    change with its batch neighbours).  Layers wider than ``max_cout``
    output channels are pinned to domain 0 — the exhaustive per-layer split
    search is O(C_out) cost evaluations.

    ``bias``: optional ``(domain_name, fraction)`` overriding the min-cost
    split on every SEARCHABLE layer: ``fraction`` of each layer's output
    channels are forced into the named domain (the rest stay digital, or
    domain 1 when the biased domain IS digital).  This is how a precision
    BANK is produced from one set of weights — e.g. on diana,
    ``bias=("aimc", 1.0)`` emits a ternary-heavy "draft" artifact and
    ``bias=("digital", 1.0)`` an int8 "target" artifact; both lower against
    the same params and bind as variants of one `repro.runtime.PlanSet`.
    """
    from repro.api import MappingArtifact, Platform
    from repro.core import baselines, quant
    from repro.core.cost_models import LayerGeometry

    plat = Platform.get(platform)
    cm, spec = plat.cost_model(), plat.spec()
    names, geoms, searchable, scales = [], [], [], []
    plan_hints = plan_hints or {}

    def w_scale(w):
        ls = float(quant.init_log_scale(np.asarray(w, dtype=np.float32)))
        return {"w_log_scales": [ls] * spec.n_domains,
                "act_log_scale": (float(act_log_scale)
                                  if act_log_scale is not None else None)}

    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        parts = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        # dense/conv layers only ({"w": ...} dicts, the repo-wide
        # convention) — other >=2-D leaves (norm scale stacks, ssm params,
        # grouped expert einsums) can never execute as channel-split matmuls
        if not parts or parts[-1] != "w":
            continue
        parts = parts[:-1]               # drop the leaf key: name the layer
        name = "/".join(parts)
        ndim = getattr(leaf, "ndim", 0)
        hint = plan_hints.get(name)
        if ndim == 2:
            names.append(name)
            geoms.append(hint[0] if hint else
                         LayerGeometry(c_in=leaf.shape[0],
                                       c_out=leaf.shape[1]))
            searchable.append((hint[1] if hint else True) and
                              leaf.shape[1] <= max_cout)
            scales.append(w_scale(leaf))
        elif ndim == 3 and parts and parts[0] in stacked_prefixes:
            # scan-stacked dense: one artifact layer per repeat
            for r in range(leaf.shape[0]):
                names.append(f"{name}@{r}")
                geoms.append(LayerGeometry(c_in=leaf.shape[1],
                                           c_out=leaf.shape[2]))
                searchable.append(leaf.shape[2] <= max_cout)
                scales.append(w_scale(leaf[r]))
        elif ndim == 4:
            kh, kw, ci, co = leaf.shape
            names.append(name)
            # façade plan geometry carries the output map (ox/oy) the cost
            # model's latency is nonlinear in; the weight shape alone can't
            geoms.append(hint[0] if hint else
                         LayerGeometry(c_in=ci, c_out=co, fx=kw, fy=kh))
            searchable.append((hint[1] if hint else True) and
                              co <= max_cout)
            scales.append(w_scale(leaf))
    assigns = baselines.min_cost(cm, geoms, "latency", searchable)
    if bias is not None:
        dom_name, frac = bias
        dom_names = [d.name for d in spec.domains]
        if dom_name not in dom_names:
            raise ValueError(f"bias domain {dom_name!r} is not on platform "
                             f"{plat.name} (domains: {dom_names})")
        if not (0.0 <= frac <= 1.0):
            raise ValueError(f"bias fraction must be in [0, 1], got {frac}")
        di = dom_names.index(dom_name)
        other = 0 if di != 0 else min(1, spec.n_domains - 1)
        for li, a in enumerate(assigns):
            if not searchable[li]:
                continue
            k = int(round(frac * a.size))
            forced = np.full(a.size, other, dtype=np.int64)
            forced[:k] = di
            assigns[li] = forced
    counts = baselines.counts_from_assignments(assigns, spec.n_domains)
    plan = [(n, g, s) for n, g, s in zip(names, geoms, searchable)]
    art = MappingArtifact.from_search(cfg.name, spec, plan, assigns, counts,
                                      platform=plat.name, objective="latency",
                                      scales=scales)
    art.save(out_path)
    print(f"[train] wrote mapping artifact ({len(names)} layers, schema v"
          f"{art.schema_version}, platform={plat.name}) -> {out_path}")
    return art


def train_cnn(args, cnn_name: str):
    """Supervised training of a CNN façade (``--arch cnn:<config>``) on the
    synthetic image task, with ``--emit-mapping`` writing the same static
    min-cost artifact the LM path writes — conv weights included, so the
    artifact lowers onto the im2col'd planned kernels
    (``serve.py --arch cnn:... --mapping``)."""
    from repro.data.pipeline import ImageTaskConfig, image_batch
    from repro.models import cnn as C

    cfg = C.get_config(cnn_name)
    init_fn, apply_fn, plan_fn = C.get_model(cfg)
    task = ImageTaskConfig(n_classes=cfg.n_classes, img_hw=cfg.img_hw,
                           in_ch=cfg.in_ch)
    params = init_fn(jax.random.PRNGKey(args.seed), cfg, None)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"[train] {cfg.name} params={n_params/1e6:.2f}M")

    ocfg = adamw.AdamWConfig(lr=args.lr, weight_decay=0.01)
    opt_state = adamw.init(params, ocfg)

    @jax.jit
    def step_fn(params, opt_state, x, y, lr):
        def loss_fn(p):
            logits = apply_fn(p, x, cfg, None, "fp", 1.0)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, gnorm = adamw.update(grads, opt_state, params,
                                                ocfg, lr=lr)
        return params, opt_state, loss

    losses = []
    for step in range(args.steps):
        lr = float(adamw.warmup_cosine(step, peak_lr=args.lr,
                                       warmup=min(args.warmup, args.steps),
                                       total=args.steps))
        x, y = image_batch(task, step, args.batch)
        params, opt_state, loss = step_fn(params, opt_state, x, y, lr)
        losses.append(float(loss))
        if step % args.log_every == 0:
            print(f"[train] step {step} loss={losses[-1]:.4f} lr={lr:.2e}")
    if args.emit_mapping:
        hints = {n: (g, s) for (n, g, s) in plan_fn(cfg)}
        emit_static_mapping(params, cfg, args.platform, args.emit_mapping,
                            plan_hints=hints, act_log_scale=args.mapping_act_scale,
                            bias=args.bias)
    print(f"[train] done. first loss={losses[0]:.4f} last={losses[-1]:.4f}")
    return losses


def make_step(cfg, ocfg, compress: bool):
    def train_step(params, opt_state, residual, batch, lr):
        loss, grads = jax.value_and_grad(
            lambda p: T.lm_loss(p, cfg, batch, remat=True))(params)
        if compress:
            comp, residual = compression.compress_with_feedback(grads, residual)
            grads = compression.decompress(comp)
        params, opt_state, gnorm = adamw.update(grads, opt_state, params,
                                                ocfg, lr=lr)
        return params, opt_state, residual, {"loss": loss, "grad_norm": gnorm}
    return train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="LM arch name, or cnn:<config> for CNN façades "
                         "(e.g. cnn:resnet20_tiny)")
    ap.add_argument("--reduce", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--platform", default="tpu_v5e",
                    help="repro.api platform name for --emit-mapping")
    ap.add_argument("--emit-mapping", default=None,
                    help="write a static min-cost mapping artifact (JSON) "
                         "for the trained weights to this path")
    ap.add_argument("--mapping-bias", default=None,
                    help="bias the emitted mapping toward a platform domain:"
                         " 'DOMAIN[:FRACTION]' forces that fraction "
                         "(default 1.0) of every searchable layer's output "
                         "channels into DOMAIN — emit a draft/target "
                         "precision bank from one set of weights (e.g. "
                         "'aimc' then 'digital' on diana)")
    ap.add_argument("--mapping-act-scale", type=float, default=None,
                    help="pin this STATIC activation log-scale on every "
                         "emitted layer (instead of dynamic per-batch "
                         "max-abs) — required for the serving engine's "
                         "per-request reproducibility and the speculative "
                         "decoder's token-identity guarantee")
    args = ap.parse_args(argv)

    args.bias = None
    if args.mapping_bias:
        if not args.emit_mapping:
            ap.error("--mapping-bias needs --emit-mapping")
        name, _, frac = args.mapping_bias.partition(":")
        args.bias = (name, float(frac) if frac else 1.0)
    if args.emit_mapping:
        from repro.api import Platform
        Platform.get(args.platform)   # unknown name fails before training
    if args.arch.startswith("cnn:"):
        return train_cnn(args, args.arch.split(":", 1)[1])

    cfgbase.load_all()
    cfg = cfgbase.get(args.arch)
    if args.reduce:
        cfg = cfgbase.reduce_for_smoke(cfg)

    ocfg = adamw.AdamWConfig(lr=args.lr, weight_decay=0.01)
    params = T.init_lm(jax.random.PRNGKey(args.seed), cfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"[train] {cfg.name} reduced={args.reduce} params={n_params/1e6:.1f}M")

    opt_state = adamw.init(params, ocfg)
    residual = (compression.init_residual(params)
                if args.compress_grads else None)

    data = ShardedLoader("token", TokenTaskConfig(vocab=cfg.vocab),
                         batch=args.batch, seq_len=args.seq)

    step_fn = jax.jit(make_step(cfg, ocfg, args.compress_grads),
                      donate_argnums=(0, 1, 2))

    start = 0
    saver = None
    if args.ckpt_dir:
        saver = ckpt.AsyncCheckpointer(args.ckpt_dir)
        if args.resume:
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                state_like = (params, opt_state)
                params, opt_state = ckpt.restore(args.ckpt_dir, latest,
                                                 state_like)
                start = ckpt.restore_extra(args.ckpt_dir, latest)["step"]
                print(f"[train] resumed from step {start}")

    straggler = StragglerPolicy()
    losses = []
    for step in range(start, args.steps):
        lr = float(adamw.warmup_cosine(step, peak_lr=args.lr,
                                       warmup=args.warmup, total=args.steps))
        tokens, targets = data.get(step)
        batch = {"tokens": tokens, "targets": targets}
        if cfg.frontend:
            batch["frontend"] = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(9), step),
                (args.batch, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        t0 = time.monotonic()
        params, opt_state, residual, metrics = step_fn(
            params, opt_state, residual, batch, lr)
        gn = float(metrics["grad_norm"])
        if not np.isfinite(gn):
            print(f"[train] step {step}: non-finite grad norm, skipped")
            continue
        dt = time.monotonic() - t0
        verdict = straggler.observe(step, dt)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            print(f"[train] step {step} loss={losses[-1]:.4f} "
                  f"gnorm={gn:.3f} lr={lr:.2e} dt={dt*1e3:.0f}ms {verdict}")
        if saver and (step + 1) % args.ckpt_every == 0:
            saver.save(step + 1, (params, opt_state), {"step": step + 1})
    if saver:
        saver.save(args.steps, (params, opt_state), {"step": args.steps})
        saver.wait()
    if args.emit_mapping:
        emit_static_mapping(params, cfg, args.platform, args.emit_mapping,
                            act_log_scale=args.mapping_act_scale,
                            bias=args.bias)
    print(f"[train] done. first loss={losses[0]:.4f} last={losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()

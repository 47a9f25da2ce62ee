"""Input specs + sharding spec trees for every (arch x shape) dry-run cell.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins (weak-type
correct, no allocation) for train/prefill/decode steps; ``*_pspecs`` build
PartitionSpec trees that mirror the exact pytree structures the model
produces (params, optimizer state, caches).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import AxisRules, param_spec_tree
from repro.models import model as M
from repro.models.layers import COMPUTE_DTYPE
from repro.train import optimizer as opt

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# Per-arch sharding strategy (DESIGN.md §5). Archs whose head count divides
# the 16-way model axis use Megatron TP over heads (default rules); the rest
# shard attention projections over head_dim and run the attention core
# sequence-parallel (shard_map). granite's 40 experts don't divide 16 ->
# experts replicated, per-expert FFN TP over d_ff. xlstm (125M) replicates
# its mixers (TP overhead exceeds any gain at that size — see §Perf).
_SEQ_CORE = {"heads": None, "head_dim": "model",
             "attn_core_seq_shard": "model"}
# serving-only extra rules: at inference there is no gradient sync, so the
# 'data' axis is free capacity for weight sharding — dbrx's 253B expert
# weights get EP over 'model' x per-expert-ff over 'data' (1.0GiB/device).
SERVE_EXTRA_RULES = {
    "dbrx-132b": {"moe_ff": ("pod", "data")},   # pod axis folds away single-pod
}

# training-only: dbrx's 253B expert weights exceed per-device HBM under pure
# 16-way EP -> FSDP the per-expert ff dim over 'data'. Inside the layer scan
# GSPMD all-gathers only the CURRENT layer's slice (true FSDP; the gradient
# transpose becomes a reduce-scatter).
TRAIN_EXTRA_RULES = {
    "dbrx-132b": {"moe_ff": ("pod", "data")},   # FSDP spans pods on 2x16x16
}

ARCH_RULES = {
    "whisper-large-v3": _SEQ_CORE,
    "starcoder2-7b": _SEQ_CORE,
    "gemma3-1b": _SEQ_CORE,
    "recurrentgemma-2b": _SEQ_CORE,
    "granite-moe-3b-a800m": {**_SEQ_CORE, "experts": None, "moe_ff": "model"},
    "xlstm-125m": {"heads": None, "head_dim": None, "rnn": None},
}


def arch_rules(mesh, arch: str, extra: Optional[dict] = None) -> AxisRules:
    rules = dict(ARCH_RULES.get(arch, {}))
    if extra:
        rules.update(extra)
    return AxisRules(mesh, rules)

WHISPER_DEC_LEN = 448  # whisper's decoder context


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def cell_applicable(cfg: M.ModelConfig, shape: str) -> tuple:
    """(runnable, reason) per DESIGN.md §Arch-applicability."""
    if shape == "long_500k" and not cfg.sub_quadratic:
        return False, "pure full-attention arch: 500k decode cache skipped"
    return True, ""


# ---------------------------------------------------------------------------
# Input specs
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: M.ModelConfig, seq: int, batch: int) -> dict:
    if cfg.enc_dec:  # whisper: encoder frames carry the seq_len
        return {
            "frames": sds((batch, seq, cfg.d_model), jnp.float32),
            "tokens": sds((batch, WHISPER_DEC_LEN), jnp.int32),
        }
    if cfg.frontend == "vision_stub":
        return {
            "patches": sds((batch, cfg.n_prefix, cfg.d_model), jnp.float32),
            "tokens": sds((batch, seq - cfg.n_prefix), jnp.int32),
        }
    return {"tokens": sds((batch, seq), jnp.int32)}


def prefill_batch_specs(cfg: M.ModelConfig, seq: int, batch: int) -> dict:
    return train_batch_specs(cfg, seq, batch)


def decode_input_specs(cfg: M.ModelConfig, seq: int, batch: int):
    """(token_sds, cache_sds) — cache via eval_shape (no allocation)."""
    token = sds((batch, 1), jnp.int32)

    def build_cache():
        if cfg.enc_dec:
            self_c = M.init_cache(cfg, batch, max_len=512)
            cross = M.init_cross_cache(cfg, batch, enc_len=seq)
            return {"self": self_c, "cross": cross}
        return {"self": M.init_cache(cfg, batch, max_len=seq), "cross": None}

    cache = jax.eval_shape(build_cache)
    return token, cache


# ---------------------------------------------------------------------------
# Sharding spec trees
# ---------------------------------------------------------------------------

def batch_pspecs(cfg: M.ModelConfig, batch_specs: dict, rules: AxisRules) -> dict:
    out = {}
    for k, v in batch_specs.items():
        names = ["batch"] + [None] * (len(v.shape) - 1)
        out[k] = rules.spec(*names)
    return out


def _kv_cache_pspec(rules: AxisRules, lead: tuple):
    return {
        "k": rules.spec(*lead, "batch", "kv_seq", "kv_heads", None),
        "v": rules.spec(*lead, "batch", "kv_seq", "kv_heads", None),
        "slot_pos": rules.spec(*lead, None),
        "pos": rules.spec(*lead),
    }


def _block_cache_pspec(cfg, kind: str, rules: AxisRules, lead: tuple):
    if kind in ("attn", "local"):
        return _kv_cache_pspec(rules, lead)
    if kind == "rec":
        return {"h": rules.spec(*lead, "batch", "rnn"),
                "conv": rules.spec(*lead, "batch", None, "rnn")}
    if kind == "mlstm":
        return {"C": rules.spec(*lead, "batch", "heads", None, None),
                "n": rules.spec(*lead, "batch", "heads", None),
                "m": rules.spec(*lead, "batch", "heads")}
    if kind == "slstm":
        v = rules.spec(*lead, "batch", "heads", None)
        return {"c": v, "n": v, "h": v, "m": v}
    raise ValueError(kind)


def cache_pspecs(cfg: M.ModelConfig, rules: AxisRules, enc_dec_cross: bool):
    scan_c = [_block_cache_pspec(cfg, kind, rules, ("none",))
              for kind in cfg.pattern] if cfg.n_periods else []
    rest_c = [_block_cache_pspec(cfg, kind, rules, ())
              for kind in cfg.rest_kinds]
    self_spec = {"scan": scan_c, "rest": rest_c}
    cross = None
    if enc_dec_cross:
        kv = rules.spec("none", "batch", "kv_seq", "kv_heads", None)
        kv1 = rules.spec("batch", "kv_seq", "kv_heads", None)
        cross = {"scan": [(kv, kv) for _ in cfg.pattern] if cfg.n_periods else [],
                 "rest": [(kv1, kv1) for _ in cfg.rest_kinds]}
    return {"self": self_spec, "cross": cross}


def zero1_specs(param_sds, base_specs, rules: AxisRules):
    """Additionally shard optimizer moments over the data axis (ZeRO-1).

    For each leaf, the first unsharded dim divisible by the data-axis size
    takes 'data'. Falls back to the base spec when nothing divides.
    """
    data_axis = rules.rules.get("batch")
    if data_axis is None:
        return base_specs
    if isinstance(data_axis, tuple):
        data_axis = data_axis[-1]  # shard moments within-pod only
    size = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))[data_axis]

    def one(sd, spec):
        entries = list(spec) + [None] * (len(sd.shape) - len(spec))
        used = set()
        for e in entries:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a:
                    used.add(a)
        if data_axis in used:
            return spec  # leaf already FSDP-sharded over the data axis
        for i, (dim, e) in enumerate(zip(sd.shape, entries)):
            if e is None and dim % size == 0 and dim >= size:
                entries[i] = data_axis
                return P(*entries)
        return spec

    return jax.tree.map(one, param_sds, base_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Cell assembly: everything dryrun.py needs for one (arch, shape)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    step_fn: object
    in_sds: tuple
    in_pspecs: tuple
    out_pspecs: object
    rules: object = None
    donate: tuple = ()


def build_cell(cfg: M.ModelConfig, arch: str, shape: str, mesh,
               n_micro: int = 1, extra_rules: Optional[dict] = None) -> Optional[Cell]:
    ok, _ = cell_applicable(cfg, shape)
    if not ok:
        return None
    info = SHAPES[shape]
    seq, batch = info["seq"], info["batch"]
    extra = dict(extra_rules or {})
    if info["kind"] in ("prefill", "decode"):
        extra = {**SERVE_EXTRA_RULES.get(arch, {}), **extra}
    if info["kind"] == "train":
        extra = {**TRAIN_EXTRA_RULES.get(arch, {}), **extra}
    if info["kind"] == "decode" and batch == 1:
        # long-context decode: replicate batch, KV sequence over data x model
        extra.setdefault("batch", None)
        extra.setdefault("kv_seq", ("data", "model"))
    rules = arch_rules(mesh, arch, extra)

    if info["kind"] == "train":
        from jax.sharding import NamedSharding
        from repro.train import loop as train_loop
        # per-microbatch batch must stay divisible by the DP degree
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_axes = rules.rules.get("batch") or ()
        dp_axes = dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)
        dp = 1
        for a in dp_axes:
            dp *= sizes.get(a, 1)
        while n_micro > 1 and (batch // n_micro) % max(dp, 1):
            n_micro //= 2
        adamw = opt.AdamWConfig()
        rng = jax.random.PRNGKey(0)
        param_sds = jax.eval_shape(functools.partial(M.init_params, cfg=cfg), rng)
        # production mixed precision: bf16 compute params + f32 master/moments
        param_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), param_sds)
        opt_sds = jax.eval_shape(opt.init, param_sds)
        batch_sds = train_batch_specs(cfg, seq, batch)

        p_specs = param_spec_tree(param_sds, rules)
        mom_specs = zero1_specs(param_sds, p_specs, rules)
        o_specs = opt.AdamWState(step=P(), mu=mom_specs, nu=mom_specs,
                                 master=mom_specs)
        grad_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), mom_specs,
                               is_leaf=lambda x: isinstance(x, P))
        step = train_loop.make_train_step(cfg, adamw, n_micro=n_micro,
                                          grad_shardings=grad_sh)
        b_specs = batch_pspecs(cfg, batch_sds, rules)
        metric_specs = {"loss": P(), "ppl_log": P(), "tokens": P(),
                        "logz_mean": P(), "grad_norm": P(), "lr": P()}
        if n_micro > 1:
            metric_specs = {"loss": P(), "grad_norm": P(), "lr": P()}
        return Cell(arch, shape, "train", step,
                    in_sds=(param_sds, opt_sds, batch_sds),
                    in_pspecs=(p_specs, o_specs, b_specs),
                    out_pspecs=(p_specs, o_specs, metric_specs),
                    rules=rules, donate=(0, 1))

    if info["kind"] == "prefill":
        def prefill_step(params, batch_in):
            return M.prefill(params, cfg, batch_in, max_len=seq)

        rng = jax.random.PRNGKey(0)
        param_sds = jax.eval_shape(functools.partial(M.init_params, cfg=cfg), rng)
        param_sds = jax.tree.map(  # serving weights are bf16
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), param_sds)
        batch_sds = prefill_batch_specs(cfg, seq, batch)
        p_specs = param_spec_tree(param_sds, rules)
        b_specs = batch_pspecs(cfg, batch_sds, rules)
        logits_spec = rules.spec("batch", None, "vocab")
        c_specs = cache_pspecs(cfg, rules, enc_dec_cross=cfg.enc_dec)
        return Cell(arch, shape, "prefill", prefill_step,
                    in_sds=(param_sds, batch_sds),
                    in_pspecs=(p_specs, b_specs),
                    out_pspecs=(logits_spec, c_specs), rules=rules)

    # decode
    def serve_step(params, token, cache):
        return M.decode_step(params, cfg, token, cache)

    rng = jax.random.PRNGKey(0)
    param_sds = jax.eval_shape(functools.partial(M.init_params, cfg=cfg), rng)
    param_sds = jax.tree.map(  # serving weights are bf16
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), param_sds)
    token_sds, cache_sds = decode_input_specs(cfg, seq, batch)
    p_specs = param_spec_tree(param_sds, rules)
    t_spec = rules.spec("batch", None)
    c_specs = cache_pspecs(cfg, rules, enc_dec_cross=cfg.enc_dec)
    logits_spec = rules.spec("batch", None, "vocab")
    return Cell(arch, shape, "decode", serve_step,
                in_sds=(param_sds, token_sds, cache_sds),
                in_pspecs=(p_specs, t_spec, c_specs),
                out_pspecs=(logits_spec, c_specs),
                rules=rules, donate=(2,))


# ---------------------------------------------------------------------------
# FCVI serving cell — the paper's technique on the production mesh
# ---------------------------------------------------------------------------

FCVI_SHAPES = {
    # 268M corpus vectors (SIFT-like d=128, m=8 filters), 1024-query batches
    "serve_268m": dict(n=1 << 28, d=128, m=8, batch=1024, k=100, kprime=400),
}


def build_fcvi_cell(shape: str, mesh, extra_rules: Optional[dict] = None,
                    variant: str = "base"):
    """Distributed FCVI query step: psi-transform -> sharded top-k'
    (tree merge over model then data axes) -> combined-score re-rank.

    Variants (§Perf hillclimb on the paper's technique):
      base  — exact f32 corpus sweep (paper-faithful FCVI-Flat)
      bf16  — bf16 transformed corpus (halves the HBM sweep; rescore stays f32)
      ivf8  — FCVI-IVF layout: each shard holds 64 lists, probes the top-8
              (1/8 of local rows scored; beyond-paper on TPU, paper-sanctioned
              backend swap)
    """
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P2
    from repro.core.transform import psi_partition
    from repro.index.distributed import sharded_search_fn

    info = FCVI_SHAPES[shape]
    n, d, m = info["n"], info["d"], info["m"]
    batch, k, kprime = info["batch"], info["k"], info["kprime"]
    lam, alpha = 0.5, 1.0
    rules = AxisRules(mesh, {**(extra_rules or {})})
    corpus_axes = tuple(a for a in ("pod", "data", "model")
                        if a in mesh.axis_names)
    n_shards = 1
    for a in corpus_axes:
        n_shards *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    corpus_dtype = jnp.bfloat16 if variant in ("bf16", "ivf8", "ivf8-trunc", "opt") else jnp.float32

    k_local = 64 if variant in ("ivf8-trunc", "opt") else 0
    local_rescore = variant == "opt"
    if variant in ("ivf8", "ivf8-trunc", "opt"):
        nlist_loc, nprobe = 64, 8
        n_loc = n // n_shards
        list_sz = n_loc // nlist_loc

        def serve_step(grouped, grouped_sq, centroids, vectors_n, filters_n,
                       q, fq):
            # grouped: (S, nlist, list_sz, d) shard-major IVF layout
            q_t = psi_partition(q, fq, alpha)

            def local(gr, gsq, cen):
                gr, gsq, cen = gr[0], gsq[0], cen[0]
                cd = q_t @ cen.T                          # (batch, nlist)
                _, probes = jax.lax.top_k(cd, nprobe)     # (batch, nprobe)

                qc = 64                                   # query chunk: bounds
                nqc = batch // qc                         # the probed gather

                def chunk(i):
                    qs = jax.lax.dynamic_slice_in_dim(q_t, i * qc, qc, 0)
                    pr = jax.lax.dynamic_slice_in_dim(probes, i * qc, qc, 0)
                    rows = gr[pr]                         # (qc, nprobe, ls, d)
                    rsq = gsq[pr]
                    sc = (2.0 * jnp.einsum("bd,bpld->bpl",
                                           qs.astype(rows.dtype), rows
                                           ).astype(jnp.float32) - rsq)
                    sc = sc.reshape(qc, nprobe * list_sz)
                    v, ix = jax.lax.top_k(sc, kprime)
                    flat = (pr[:, :, None] * list_sz
                            + jnp.arange(list_sz)[None, None, :]
                            ).reshape(qc, -1)
                    return v, jnp.take_along_axis(flat, ix, axis=-1)

                _, (vals, gidx) = jax.lax.scan(
                    lambda _, i: (None, chunk(i)), None, jnp.arange(nqc))
                vals = vals.reshape(batch, kprime)
                gidx = gidx.reshape(batch, kprime)
                if k_local:  # truncate candidates before the merge tree
                    vals = vals[:, :k_local]
                    gidx = gidx[:, :k_local]
                # globalise ids and tree-merge over the corpus axes
                offset = jnp.int32(0)
                stride = n_loc
                for ax in reversed(corpus_axes):
                    offset = offset + jax.lax.axis_index(ax) * stride
                    stride = stride * jax.lax.axis_size(ax)
                gidx = gidx + offset
                from repro.index.distributed import _merge_over_axis
                for i, ax in enumerate(reversed(corpus_axes)):
                    keep = kprime if i == len(corpus_axes) - 1 else \
                        (k_local or kprime)
                    vals, gidx = _merge_over_axis(vals, gidx, ax, keep)
                return vals, gidx

            _, cand = jax.shard_map(
                local, mesh=mesh,
                in_specs=(P2(corpus_axes), P2(corpus_axes), P2(corpus_axes)),
                out_specs=(P2(), P2()), check_vma=False)(
                grouped, grouped_sq, centroids)

            if local_rescore:
                # compute-to-data re-scoring: each shard scores ITS candidate
                # rows and psums 4 small (b, k') partials — the 210MB
                # candidate-vector gather becomes ~6MB of score traffic.
                def rescore(vn, fn):
                    n_loc2 = vn.shape[0]
                    offset = jnp.int32(0)
                    stride = n_loc2
                    for ax in reversed(corpus_axes):
                        offset = offset + jax.lax.axis_index(ax) * stride
                        stride = stride * jax.lax.axis_size(ax)
                    lid = cand - offset
                    own = (lid >= 0) & (lid < n_loc2)
                    safe = jnp.clip(lid, 0, n_loc2 - 1)
                    cv = vn[safe] * own[..., None]
                    cf = fn[safe] * own[..., None]
                    parts = jnp.stack([
                        jnp.sum(cv * q[:, None, :], -1),
                        jnp.linalg.norm(cv, axis=-1),
                        jnp.sum(cf * fq[:, None, :], -1),
                        jnp.linalg.norm(cf, axis=-1)])
                    for ax in corpus_axes:
                        parts = jax.lax.psum(parts, ax)
                    nv, dv, nf, df = parts
                    qn = jnp.linalg.norm(q, axis=-1)[:, None]
                    fqn = jnp.linalg.norm(fq, axis=-1)[:, None]
                    return (lam * nv / (dv * qn + 1e-8)
                            + (1 - lam) * nf / (df * fqn + 1e-8))

                score = jax.shard_map(
                    rescore, mesh=mesh,
                    in_specs=(P2(corpus_axes), P2(corpus_axes)),
                    out_specs=P2(), check_vma=False)(vectors_n, filters_n)
            else:
                cv = vectors_n[cand].astype(jnp.float32)
                cf = filters_n[cand]

                def cos(candt, qv):
                    num = jnp.sum(candt * qv[:, None, :], axis=-1)
                    den = (jnp.linalg.norm(candt, axis=-1)
                           * jnp.linalg.norm(qv, axis=-1)[:, None] + 1e-8)
                    return num / den

                score = lam * cos(cv, q) + (1 - lam) * cos(cf, fq)
            vals, pos = jax.lax.top_k(score, k)
            return vals, jnp.take_along_axis(cand, pos, axis=-1)

        in_sds = (
            sds((n_shards, nlist_loc, list_sz, d), corpus_dtype),
            sds((n_shards, nlist_loc, list_sz), jnp.float32),
            sds((n_shards, nlist_loc, d), jnp.float32),
            sds((n, d), jnp.float32), sds((n, m), jnp.float32),
            sds((batch, d), jnp.float32), sds((batch, m), jnp.float32),
        )
        row = P(corpus_axes)
        in_pspecs = (row, row, row, P(corpus_axes, None),
                     P(corpus_axes, None), P(), P())
        return Cell("fcvi", shape, "fcvi_serve", serve_step,
                    in_sds=in_sds, in_pspecs=in_pspecs,
                    out_pspecs=(P(), P()), rules=rules)

    search = sharded_search_fn(mesh, corpus_axes, kprime,
                               k_local=k_local)

    def serve_step(corpus_t, sq_norms, vectors_n, filters_n, q, fq):
        q_t = psi_partition(q, fq, alpha).astype(corpus_dtype)
        _, cand = search(corpus_t, sq_norms, q_t)          # (batch, k')
        cv = vectors_n[cand].astype(jnp.float32)           # (batch, k', d)
        cf = filters_n[cand]

        def cos(cand, qv):  # cand: (b, k', x); qv: (b, x)
            num = jnp.sum(cand * qv[:, None, :], axis=-1)
            den = (jnp.linalg.norm(cand, axis=-1)
                   * jnp.linalg.norm(qv, axis=-1)[:, None] + 1e-8)
            return num / den

        score = lam * cos(cv, q) + (1 - lam) * cos(cf, fq)
        vals, pos = jax.lax.top_k(score, k)
        return vals, jnp.take_along_axis(cand, pos, axis=-1)

    row = P(corpus_axes)
    in_sds = (
        sds((n, d), corpus_dtype), sds((n,), jnp.float32),
        sds((n, d), jnp.float32), sds((n, m), jnp.float32),
        sds((batch, d), jnp.float32), sds((batch, m), jnp.float32),
    )
    in_pspecs = (P(corpus_axes, None), row, P(corpus_axes, None),
                 P(corpus_axes, None), P(), P())
    out_pspecs = (P(), P())
    return Cell("fcvi", shape, "fcvi_serve", serve_step,
                in_sds=in_sds, in_pspecs=in_pspecs, out_pspecs=out_pspecs,
                rules=rules)

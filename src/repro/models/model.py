"""Generic LM covering all 10 assigned architectures.

One scan over homogeneous layer *groups* (a group = one tile of
cfg.pattern, e.g. ('rec','rec','attn') for recurrentgemma); leftover
layers run unrolled. Params for scanned groups are stacked along a
leading 'layers' axis, so compile time is O(1) in depth.

The paper's technique is integrated end-to-end: every projection weight
is DBB-tagged (Param.dbb), `constrain()` projects params onto the block
constraint during training, and `compress()` converts them to the
compressed DBBWeight layout for serving (apply_linear then runs the
time-unrolled compressed matmul).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.act_sparsity import act_scope
from repro.core.sparse_linear import PruneSchedule
from repro.core.vdbb import DBBFormat, DBBWeight, dbb_encode, dbb_prune
from repro.models.attention import GQAttention, MLAttention
from repro.models.common import (
    Param,
    abstract_params,
    apply_linear,
    dbb_leaves,
    init_params,
    layer_norm,
    param_pspecs,
    rms_norm,
    shard,
    tree_get,
    tree_set,
)
from repro.models.config import ModelConfig
from repro.models.mlp import DenseMLP, MoEMLP
from repro.models.recurrent import RGLRUBlock, RWKV6Block


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # ------------------------------------------------------------- defs
    def _mixer(self, kind):
        c = self.cfg
        if kind == "attn":
            return MLAttention(c) if c.mixer == "mla" else GQAttention(c)
        if kind == "local":
            return GQAttention(c, window=c.local_window)
        if kind == "rec":
            return RGLRUBlock(c)
        if kind == "rwkv":
            return RWKV6Block(c)
        raise ValueError(kind)

    def _mlp(self):
        c = self.cfg
        return MoEMLP(c) if c.is_moe else DenseMLP(c)

    def _norm_def(self):
        c = self.cfg
        d = {"g": Param((c.d_model,), (None,), "ones")}
        if c.norm == "layernorm":
            d["b"] = Param((c.d_model,), (None,), "zeros")
        return d

    def _apply_norm(self, p, x):
        if self.cfg.norm == "layernorm":
            return layer_norm(x, p["g"], p["b"])
        return rms_norm(x, p["g"])

    def _block_defs(self, kind):
        c = self.cfg
        d = {"norm1": self._norm_def(), "mixer": self._mixer(kind).defs()}
        if kind == "rwkv":
            d["norm2"] = self._norm_def()
            return d
        d["norm2"] = self._norm_def()
        d["mlp"] = self._mlp().defs()
        if c.cross_attn:
            d["norm_x"] = self._norm_def()
            d["cross"] = GQAttention(c, cross=True).defs()
        return d

    def defs(self):
        c = self.cfg
        group = {f"b{i}": self._block_defs(k) for i, k in enumerate(c.pattern)}
        stacked = jax.tree_util.tree_map(
            lambda p: dataclasses.replace(
                p, shape=(c.num_groups,) + p.shape, axes=("layers",) + p.axes
            ),
            group,
            is_leaf=lambda x: isinstance(x, Param),
        )
        out = {
            "embed": Param((c.padded_vocab, c.d_model), ("vocab", "embed"), "scaled"),
            "layers": stacked,
            "final_norm": self._norm_def(),
        }
        if c.tail_pattern:
            out["tail"] = {
                f"t{i}": self._block_defs(k) for i, k in enumerate(c.tail_pattern)
            }
        if not c.tie_embeddings:
            head_v = (
                c.num_codebooks * c.codebook_vocab
                if c.frontend == "audio"
                else c.padded_vocab
            )
            out["lm_head"] = Param((c.d_model, head_v), ("embed", "vocab"), "scaled")
        if c.frontend == "audio":
            out["embed"] = Param(
                (c.num_codebooks, c.codebook_vocab, c.d_model),
                (None, "vocab", "embed"),
                "scaled",
            )
        return out

    def init(self, key):
        return init_params(self.defs(), key, self.cfg.param_dtype)

    def abstract(self):
        return abstract_params(self.defs(), self.cfg.param_dtype)

    def pspecs(self, rules: dict):
        return param_pspecs(self.defs(), rules)

    # ------------------------------------------------------- embeddings
    def _embed(self, params, batch):
        c = self.cfg
        from repro.models.common import sharded_embed_lookup

        tok = batch["tokens"]
        if c.frontend == "audio":
            # tok: (B,S,ncb) — sum codebook embeddings (tiny 2048-row tables:
            # plain take, replicated-friendly)
            embs = [
                jnp.take(params["embed"][i], tok[..., i], axis=0)
                for i in range(c.num_codebooks)
            ]
            h = sum(embs).astype(c.compute_dtype)
        else:
            h = sharded_embed_lookup(params["embed"], tok, c.compute_dtype)
        if c.embed_scale:
            h = h * jnp.sqrt(float(c.d_model)).astype(c.compute_dtype)
        if c.frontend == "vision" and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(c.compute_dtype)
            h = jax.lax.dynamic_update_slice(h, ve, (0, 0, 0))
        return shard(h, ("batch", "seq", "embed"))

    def _logits(self, params, x):
        c = self.cfg
        if c.tie_embeddings:
            logits = x @ params["embed"].T.astype(x.dtype)
        else:
            logits = apply_linear(x, params["lm_head"],
                                  kernel_mode=c.kernel_mode, name="lm_head")
        if c.logit_softcap:
            logits = jnp.tanh(logits / c.logit_softcap) * c.logit_softcap
        # note: 'seq' (SP) and 'vocab' both map to 'model' — logits keep the
        # vocab shard and replicate seq (bounded: B*S*V/tp elements).
        return shard(logits, ("batch", None, "vocab"))

    # ----------------------------------------------------------- blocks
    def _apply_block(self, kind, p, x, positions, memory):
        """Full-sequence block. Returns (x, cache_for_this_block)."""
        c = self.cfg
        h = self._apply_norm(p["norm1"], x)
        mixer = self._mixer(kind)
        if kind == "rwkv":
            b = x.shape[0]
            zero = jnp.zeros((b, c.d_model), x.dtype)
            y, tm_cache = mixer.time_mix(p["mixer"]["tm"], h, zero)
            x = shard(x + y, ("batch", "seq", "embed"))
            h2 = self._apply_norm(p["norm2"], x)
            y2, cm_shift = mixer.channel_mix(p["mixer"]["cm"], h2, zero)
            x = shard(x + y2, ("batch", "seq", "embed"))
            return x, {**tm_cache, "cm_shift": cm_shift}
        with act_scope("mixer"):
            y, cache = mixer(p["mixer"], h, positions)
        x = shard(x + y, ("batch", "seq", "embed"))
        if c.cross_attn:
            hx = self._apply_norm(p["norm_x"], x)
            with act_scope("cross"):
                yx, xc = GQAttention(c, cross=True)(
                    p["cross"], hx, positions, memory=memory
                )
            x = shard(x + yx, ("batch", "seq", "embed"))
            cache = {"self": cache, "cross": xc}
        with act_scope("mlp"):
            y2 = self._mlp()(p["mlp"], self._apply_norm(p["norm2"], x))
        x = shard(x + y2, ("batch", "seq", "embed"))
        return x, cache

    def _apply_block_decode(self, kind, p, x, cache, pos):
        c = self.cfg
        h = self._apply_norm(p["norm1"], x)
        mixer = self._mixer(kind)
        if kind == "rwkv":
            y, tm_cache = mixer.time_mix_decode(p["mixer"]["tm"], h, cache)
            x = x + y
            h2 = self._apply_norm(p["norm2"], x)
            y2, cm_shift = mixer.channel_mix_decode(p["mixer"]["cm"], h2, cache["cm_shift"])
            return x + y2, {**tm_cache, "cm_shift": cm_shift}
        if c.cross_attn:
            y, new_self = mixer.decode(p["mixer"], h, cache["self"], pos)
            x = x + y
            hx = self._apply_norm(p["norm_x"], x)
            yx, _ = GQAttention(c, cross=True).decode(p["cross"], hx, cache["cross"], pos)
            x = x + yx
            new_cache = {"self": new_self, "cross": cache["cross"]}
        else:
            y, new_cache = mixer.decode(p["mixer"], h, cache, pos)
            x = x + y
        y2 = self._mlp()(p["mlp"], self._apply_norm(p["norm2"], x))
        return x + y2, new_cache

    # ---------------------------------------------------------- forward
    def forward(
        self,
        params,
        batch,
        *,
        return_cache: bool = False,
        collect_act_stats: bool = False,
        act_threshold: float = 0.0,
    ):
        """Full-sequence forward (train / prefill). Returns (logits, cache).

        ``collect_act_stats=True`` (eager-only; DESIGN.md §7) additionally
        returns the per-GEMM ActStats recorded by ``apply_linear``: the
        result becomes ``(logits[, cache], stats)``. While collecting, the
        scan/remat paths are bypassed (their bodies are traced, so there
        would be nothing concrete to measure).
        """
        if collect_act_stats:
            from repro.core.act_sparsity import collect_activations

            with collect_activations(threshold=act_threshold) as col:
                out = self.forward(params, batch, return_cache=return_cache)
            out = out if isinstance(out, tuple) else (out,)
            return (*out, col.stats)
        from repro.core.act_sparsity import collecting

        c = self.cfg
        h = self._embed(params, batch)
        b, s, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        memory = batch.get("memory")
        if memory is not None:
            memory = memory.astype(c.compute_dtype)

        def group_body(x, gp):
            caches = {}
            for i, kind in enumerate(c.pattern):
                with act_scope(f"b{i}"):
                    x, cache = self._apply_block(
                        kind, gp[f"b{i}"], x, positions, memory
                    )
                caches[f"b{i}"] = cache
            return x, caches

        body = group_body
        if not collecting():  # remat/scan trace the body: skip while measuring
            if c.remat == "full":
                body = jax.checkpoint(group_body, prevent_cse=False)
            elif c.remat == "dots":
                body = jax.checkpoint(
                    group_body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    prevent_cse=False,
                )
        if c.scan_layers and not collecting():
            h, caches = jax.lax.scan(body, h, params["layers"])
        else:
            caches_l = []
            for g in range(c.num_groups):
                gp = jax.tree_util.tree_map(lambda a: a[g], params["layers"])
                with act_scope(f"g{g}"):
                    h, cch = body(h, gp)
                caches_l.append(cch)
            caches = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches_l)
        if c.tail_pattern:
            tails = {}
            for i, kind in enumerate(c.tail_pattern):
                with act_scope("tail"), act_scope(f"t{i}"):
                    h, cache = self._apply_block(
                        kind, params["tail"][f"t{i}"], h, positions, memory
                    )
                tails[f"t{i}"] = cache
            caches = {"groups": caches, "tail": tails}
        else:
            caches = {"groups": caches}
        h = self._apply_norm(params["final_norm"], h)
        logits = self._logits(params, h)
        if return_cache:
            return logits, caches
        return logits

    # ------------------------------------------------------------- loss
    def loss(self, params, batch):
        c = self.cfg
        logits = self.forward(params, batch)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if c.frontend == "audio":
            bsz, s, _ = logits.shape
            logits = logits.reshape(bsz, s, c.num_codebooks, c.codebook_vocab)
            vocab = c.codebook_vocab
        else:
            vocab = logits.shape[-1]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        iota = jnp.arange(vocab, dtype=labels.dtype)
        onehot = (labels[..., None] == iota).astype(jnp.float32)
        label_logit = jnp.sum(logits.astype(jnp.float32) * onehot, axis=-1)
        nll = lse - label_logit
        if c.frontend == "audio":
            nll = nll.mean(-1)
        if mask is not None:
            nll = nll * mask
            denom = jnp.maximum(mask.sum(), 1.0)
        else:
            denom = float(nll.size)
        loss = nll.sum() / denom
        return loss, {"loss": loss, "nll_mean": loss}

    # ------------------------------------------------------------ cache
    def init_cache(self, batch_size: int, max_len: int):
        c = self.cfg
        dt = c.compute_dtype

        def block_cache(kind):
            if kind == "rwkv":
                return self._mixer(kind).init_cache(batch_size, max_len, dt)
            cc = self._mixer(kind).init_cache(batch_size, max_len, dt)
            if c.cross_attn:
                cc = {
                    "self": cc,
                    "cross": GQAttention(c, cross=True).init_cache(batch_size, max_len, dt),
                }
            return cc

        group = {f"b{i}": block_cache(k) for i, k in enumerate(c.pattern)}
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (c.num_groups,) + a.shape), group
        )
        out = {"groups": stacked}
        if c.tail_pattern:
            out["tail"] = {
                f"t{i}": block_cache(k) for i, k in enumerate(c.tail_pattern)
            }
        return out

    def cache_abstract(self, batch_size: int, max_len: int):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.eval_shape(lambda: self.init_cache(batch_size, max_len)),
        )

    # ------------------------------------------------------ decode step
    def decode_step(self, params, cache, batch, pos):
        """One-token decode. batch['tokens']: (B,1[,ncb]); pos: scalar."""
        c = self.cfg
        h = self._embed(params, batch)

        def group_body(x, scanned):
            gp, gc = scanned
            new = {}
            for i, kind in enumerate(c.pattern):
                x, nc = self._apply_block_decode(kind, gp[f"b{i}"], x, gc[f"b{i}"], pos)
                new[f"b{i}"] = nc
            return x, new

        if c.scan_layers:
            h, new_groups = jax.lax.scan(group_body, h, (params["layers"], cache["groups"]))
        else:
            outs = []
            for g in range(c.num_groups):
                gp = jax.tree_util.tree_map(lambda a: a[g], params["layers"])
                gc = jax.tree_util.tree_map(lambda a: a[g], cache["groups"])
                h, nc = group_body(h, (gp, gc))
                outs.append(nc)
            new_groups = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
        new_cache = {"groups": new_groups}
        if c.tail_pattern:
            tails = {}
            for i, kind in enumerate(c.tail_pattern):
                h, nc = self._apply_block_decode(
                    kind, params["tail"][f"t{i}"], h, cache["tail"][f"t{i}"], pos
                )
                tails[f"t{i}"] = nc
            new_cache["tail"] = tails
        h = self._apply_norm(params["final_norm"], h)
        logits = self._logits(params, h)
        return logits, new_cache

    _CACHE_AXES = {
        "k": ("batch", "cache_seq", "kv", None),
        "v": ("batch", "cache_seq", "kv", None),
        "c_kv": ("batch", "cache_seq", None),
        "k_rope": ("batch", "cache_seq", None),
        "h": ("batch", "mlp"),
        "conv": ("batch", None, "mlp"),
        "s": ("batch", None, None, None),
        "shift": ("batch", None),
        "cm_shift": ("batch", None),
    }

    def cache_pspecs(self, rules: dict):
        """PartitionSpec tree matching init_cache structure (key-driven)."""
        from jax.sharding import PartitionSpec as P

        ab = self.cache_abstract(2, 4)
        flat, treedef = jax.tree_util.tree_flatten_with_path(ab)
        specs = []
        for path, leaf in flat:
            name = path[-1].key
            axes = self._CACHE_AXES[name]
            if leaf.ndim == len(axes) + 1:  # stacked over scanned groups
                axes = (None,) + tuple(axes)
            assert leaf.ndim == len(axes), (path, leaf.shape, axes)
            specs.append(P(*(rules.get(a) for a in axes)))
        return jax.tree_util.tree_unflatten(treedef, specs)

    # --------------------------------------------- the paper's technique
    def _dbb_apply(self, w, fmt: DBBFormat, fn):
        """Apply a (K,N)->... DBB op through leading stack dims via vmap."""
        f = fn
        for _ in range(w.ndim - 2):
            f = jax.vmap(f)
        return f(w)

    def constrain(self, params, step=None, schedule: Optional[PruneSchedule] = None):
        """Project every DBB-tagged weight onto the (annealed) constraint."""
        for path, pdef in dbb_leaves(self.defs()):
            fmt = pdef.dbb
            w = tree_get(params, path)
            if not isinstance(w, jnp.ndarray):
                continue  # already compressed
            if schedule is not None and step is not None:
                nnzs = list(range(fmt.nnz, fmt.bz + 1))
                cur = schedule.nnz_at(step, fmt)
                branches = [
                    partial(
                        self._dbb_apply,
                        fmt=dataclasses.replace(fmt, nnz=n),
                        fn=lambda x, n=n: dbb_prune(x, dataclasses.replace(fmt, nnz=n)),
                    )
                    for n in nnzs
                ]
                w = jax.lax.switch(cur - fmt.nnz, branches, w)
            else:
                w = self._dbb_apply(w, fmt, lambda x: dbb_prune(x, fmt))
            params = tree_set(params, path, w)
        return params

    def compress(self, params):
        """Encode DBB-tagged weights into compressed DBBWeight for serving.

        Stacked-layer weights (leading 'layers' dim) are encoded with a
        batched leading axis — lax.scan slices them per layer. 4-D expert
        stacks stay dense-with-zeros (DESIGN.md §5)."""
        for path, pdef in dbb_leaves(self.defs()):
            fmt = pdef.dbb
            w = tree_get(params, path)
            if not isinstance(w, jnp.ndarray) or w.ndim > 3:
                continue
            if w.ndim == 2:
                dw = dbb_encode(w, fmt, prune=True)
            else:
                dw = jax.vmap(lambda x: dbb_encode(x, fmt, prune=True))(w)
            params = tree_set(params, path, dw)
        return params

    def _stat_absmax(self, stats) -> dict:
        """name -> max absmax over the calibration records (a name repeats
        when the same GEMM ran more than once during calibration)."""
        out = {}
        for st in stats or []:
            name = getattr(st, "name", "")
            amax = float(getattr(st, "absmax", 0.0))
            if name and amax > 0.0:
                out[name] = max(out.get(name, 0.0), amax)
        return out

    def _leaf_act_scales(self, path, absmax):
        """Calibrated per-tensor act scale(s) for one dbb leaf, or None.

        Stacked leaves (``('layers', 'b{i}', …)``) look up one scoped name
        per layer group (``g{g}.b{i}.….<leaf>``) and return an (L,) array —
        ``lax.scan`` slices it back to a scalar per layer; tail leaves use
        their dotted path directly. Missing calibration → None (the serving
        path falls back to dynamic quantization).
        """
        from repro.core.quant import QMAX

        if path[0] == "layers":
            suffix = ".".join(path[1:])
            scales = []
            for g in range(self.cfg.num_groups):
                amax = absmax.get(f"g{g}.{suffix}")
                if amax is None:
                    return None
                scales.append(amax / QMAX)
            return jnp.asarray(scales, jnp.float32)
        amax = absmax.get(".".join(path))
        if amax is None:
            return None
        return jnp.float32(amax / QMAX)

    def quantize(self, params, stats=None):
        """INT8-quantize every compressed DBBWeight leaf (DESIGN.md §8/§13).

        ``stats`` is the list returned by
        ``forward(..., collect_act_stats=True)`` run on *compressed* params:
        each leaf whose scoped activation name was calibrated gets a static
        per-tensor act scale stored as a ``<leaf>_aq`` sibling, which
        ``apply_linear`` picks up (and the §9 int8-resident MLP chain keys
        on); uncalibrated leaves serve with dynamic quantization.
        """
        from repro.core.quant import quantize_dbb

        absmax = self._stat_absmax(stats)
        for path, _pdef in dbb_leaves(self.defs()):
            w = tree_get(params, path)
            if not isinstance(w, DBBWeight):
                continue  # dense (never compressed) or already quantized
            qw = quantize_dbb(w) if w.values.ndim == 3 else jax.vmap(quantize_dbb)(w)
            params = tree_set(params, path, qw)
            aq = self._leaf_act_scales(path, absmax)
            if aq is not None:
                params = tree_set(params, path[:-1] + (path[-1] + "_aq",), aq)
        return params

    # ------------------------------------------------------------- plan
    def plan(self, params, *, batch: int, seq: int):
        """Freeze a serving plan for a fixed (batch, seq) shape (§13).

        Stages: ``embed`` → one stage per block (layer groups unrolled:
        ``g{g}.b{i}``, then tail ``t{i}``) → ``head`` (final norm +
        logits). Composition and staleness come from the shared
        :class:`~repro.models.plan.ModelPlan` machinery, exactly like the
        CNN plan. One deviation from the CNN: LM stages carry empty
        ``tiles`` — a transformer block mixes several GEMMs per stage, and
        each resolves ``kernels/core.py``'s tile rule for its own shape
        when the stage is traced (the rule the CNN layers freeze).

        Raises ``NotImplementedError`` for cross-attention / multimodal
        configs: their blocks need extra per-call inputs (memory, vision
        embeds) that a frozen single-input pipeline cannot bind.
        """
        from repro.models.plan import PlanBuilder

        c = self.cfg
        if c.cross_attn or c.frontend:
            raise NotImplementedError(
                "LM.plan supports decoder-only text models; cross_attn or "
                f"frontend={c.frontend!r} needs per-call side inputs")
        positions = jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
        pb = PlanBuilder(c.name, params, batch=batch,
                         sample_spec=((seq,), "int32"))
        pb.raw("embed", "embed", lambda t: self._embed(params, {"tokens": t}))
        for g in range(c.num_groups):
            gp = jax.tree_util.tree_map(lambda a, _g=g: a[_g],
                                        params["layers"])
            for i, kind in enumerate(c.pattern):
                pb.raw(
                    f"g{g}.b{i}", kind,
                    lambda x, p=gp[f"b{i}"], k=kind:
                        self._apply_block(k, p, x, positions, None)[0],
                )
        for i, kind in enumerate(c.tail_pattern):
            pb.raw(
                f"t{i}", kind,
                lambda x, p=params["tail"][f"t{i}"], k=kind:
                    self._apply_block(k, p, x, positions, None)[0],
            )
        pb.raw("head", "head",
               lambda x: self._logits(
                   params, self._apply_norm(params["final_norm"], x)))
        return pb.build()

    def compressed_abstract(self):
        """ShapeDtypeStruct tree of the *compressed* serving params."""
        return jax.eval_shape(lambda p: self.compress(p), self.abstract())

    def compressed_pspecs(self, rules: dict):
        """PartitionSpecs matching compress() output."""
        from jax.sharding import PartitionSpec as P

        specs = self.pspecs(rules)
        for path, pdef in dbb_leaves(self.defs()):
            if len(pdef.shape) > 3:
                continue
            base = tree_get(specs, path)  # P over (maybe layers,) K, N
            parts = tuple(base)
            if len(pdef.shape) == 2:
                k_ax, n_ax = parts
                vals = P(k_ax, None, n_ax)
                idx = P(k_ax, None, None)
            else:
                l_ax, k_ax, n_ax = parts
                vals = P(l_ax, k_ax, None, n_ax)
                idx = P(l_ax, k_ax, None, None)
            from repro.core.vdbb import DBBWeight

            dw_spec = DBBWeight(vals, idx, pdef.dbb, pdef.shape[-2:])
            specs = tree_set(specs, path, dw_spec)
        return specs

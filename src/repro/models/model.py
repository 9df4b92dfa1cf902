"""Model facade: embedding + stack + LM head, with train / prefill / decode
entry points for every assigned family, plus ``input_specs`` used by the
multi-pod dry-run (ShapeDtypeStruct stand-ins, no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.distributed.hints import constrain
from repro.models import transformer as tfm
from repro.models.layers import (Params, embed_init, init_rmsnorm, rmsnorm,
                                 scale, scale_embedding, sinusoidal_positions,
                                 softmax_cross_entropy)

Cache = Dict[str, Any]


class Model:
    """Functional model wrapper for one ``ModelConfig``.

    All methods are pure functions of (params, inputs) and jit-able; the
    class only holds static configuration.
    """

    def __init__(self, config: ModelConfig, param_dtype=jnp.bfloat16,
                 remat: bool = False, kv_quant: bool = False):
        self.cfg = config
        self.dtype = param_dtype
        self.remat = remat
        # int8 KV cache (§Perf K1) — decoder-only attention caches
        self.kv_quant = kv_quant and config.arch_type not in ("ssm", "audio")

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_head, k_enc, k_extra = jax.random.split(rng, 5)
        params: Params = {
            "embed": embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), self.dtype),
            "final_norm": init_rmsnorm(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(
                k_head, (cfg.d_model, cfg.padded_vocab), self.dtype)
        if cfg.is_encdec:
            params["enc_blocks"] = jax.vmap(
                lambda k: tfm.init_encoder_block(k, cfg, self.dtype)
            )(jax.random.split(k_enc, cfg.num_encoder_layers))
            params["enc_norm"] = init_rmsnorm(cfg.d_model, self.dtype)
            params["blocks"] = jax.vmap(
                lambda k: tfm.init_decoder_block_encdec(k, cfg, self.dtype)
            )(jax.random.split(k_blocks, cfg.num_layers))
        else:
            params["blocks"] = tfm.init_stacked_blocks(k_blocks, cfg, self.dtype)
        if cfg.arch_type == "vlm":
            # projector stub: patch embeddings arrive pre-projected; keep a
            # learned scale so the projector path has params end-to-end.
            params["patch_scale"] = jnp.ones((cfg.d_model,), self.dtype)
        return params

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _flags(self) -> jax.Array:
        return jnp.asarray(self.cfg.global_layer_flags())

    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        return scale_embedding(params["embed"][tokens], self.cfg.d_model,
                               self.cfg.mult.embedding)

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        x = rmsnorm(x, params["final_norm"], self.cfg.norm_eps)
        if self.cfg.tie_embeddings:
            return scale(x @ params["embed"].T, self.cfg.mult.lm_head)
        return scale(x @ params["unembed"], self.cfg.mult.lm_head)

    def _encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """Whisper encoder over stubbed frame embeddings [B, T, d]."""
        pos = sinusoidal_positions(frames.shape[1], self.cfg.d_model)
        x = frames + pos[None].astype(frames.dtype)
        x = tfm.encoder_stack(params["enc_blocks"], x, self.cfg,
                              remat=self.remat)
        return rmsnorm(x, params["enc_norm"], self.cfg.norm_eps)

    def _decoder_input(self, params: Params, batch: Dict[str, jax.Array]
                       ) -> jax.Array:
        """Build the decoder-stack input embedding for this family."""
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        if cfg.arch_type == "vlm":
            patches = batch["patch_embeds"].astype(x.dtype)
            patches = patches * params["patch_scale"]
            x = jnp.concatenate([patches, x], axis=1)
        if cfg.is_encdec:
            pos = sinusoidal_positions(x.shape[1], cfg.d_model)
            x = x + pos[None].astype(x.dtype)
        return constrain(x, "btd")

    # ------------------------------------------------------------------
    # training forward / loss
    # ------------------------------------------------------------------
    def forward(self, params: Params, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward. Returns (logits, moe_aux_loss)."""
        y, aux = self._hidden(params, batch)
        return self._logits(params, y), aux

    # sequence-chunk size for the CE loss: never materialize [B, S, V]
    LOSS_CHUNK = 512

    def _hidden(self, params: Params, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward up to the final hidden states."""
        cfg = self.cfg
        x = self._decoder_input(params, batch)
        if cfg.is_encdec:
            mem = self._encode(params, batch["frames"])
            y = tfm.encdec_decoder_full(params["blocks"], x, mem, cfg,
                                        remat=self.remat)
            aux = jnp.zeros((), jnp.float32)
        else:
            y, aux = tfm.stack_full(params["blocks"], x, cfg, self._flags(),
                                    remat=self.remat)
        return y, aux

    def _chunked_ce(self, params: Params, y: jax.Array, labels: jax.Array,
                    mask: Optional[jax.Array]) -> jax.Array:
        """CE over sequence chunks — logits live one [B, c, V] slab at a
        time (rematerialized in backward), essential for 256k vocabularies."""
        B, S, _ = y.shape
        c = min(self.LOSS_CHUNK, S)
        if S % c:
            c = S  # irregular smoke shapes: single chunk
        nc = S // c
        yc = y.reshape(B, nc, c, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, nc, c).transpose(1, 0, 2)
        mc = (mask if mask is not None
              else jnp.ones((B, S), jnp.float32)).reshape(
                  B, nc, c).transpose(1, 0, 2)

        def body(carry, inp):
            ych, lch, mch = inp
            logits = self._logits(params, constrain(ych, "btd"))
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lch[..., None],
                                       axis=-1)[..., 0]
            nll = (logz - gold) * mch
            return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(mch)), None

        body = jax.checkpoint(body, prevent_cse=False)
        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (yc, lc, mc))
        return tot / jnp.maximum(cnt, 1.0)

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        y, aux = self._hidden(params, batch)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if cfg.arch_type == "vlm":
            # image-patch positions carry no next-token target
            P = cfg.num_patch_tokens
            pad = jnp.zeros(labels.shape[:1] + (P,), labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
            m = jnp.concatenate(
                [jnp.zeros(labels.shape[:1] + (P,), jnp.float32),
                 jnp.ones(batch["labels"].shape, jnp.float32)], axis=1)
            mask = m if mask is None else mask * m
        ce = self._chunked_ce(params, y, labels, mask)
        if cfg.has_moe:
            ce = ce + cfg.moe.aux_loss_weight * aux
        return ce

    # ------------------------------------------------------------------
    # serving: prefill + decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, seq_len: int,
                   enc_len: Optional[int] = None) -> Cache:
        """Zeroed decode cache with room for ``seq_len`` positions."""
        cfg = self.cfg
        L, hd = cfg.num_layers, cfg.resolved_head_dim
        cache: Cache = {"pos": jnp.zeros((batch,), jnp.int32)}
        layers: Dict[str, jax.Array] = {}
        if cfg.arch_type != "ssm":
            kv_dtype = jnp.int8 if self.kv_quant else self.dtype
            layers["k"] = jnp.zeros((L, batch, cfg.num_kv_heads, seq_len, hd),
                                    kv_dtype)
            layers["v"] = jnp.zeros((L, batch, cfg.num_kv_heads, seq_len, hd),
                                    kv_dtype)
            if self.kv_quant:
                layers["k_scale"] = jnp.zeros(
                    (L, batch, cfg.num_kv_heads, seq_len, 1), self.dtype)
                layers["v_scale"] = jnp.zeros(
                    (L, batch, cfg.num_kv_heads, seq_len, 1), self.dtype)
        if cfg.has_ssm:
            s = cfg.ssm
            H = s.num_heads(cfg.d_model)
            layers["conv"] = jnp.zeros(
                (L, batch, s.d_conv - 1, s.conv_dim(cfg.d_model)), self.dtype)
            layers["h"] = jnp.zeros((L, batch, H, s.head_dim, s.d_state),
                                    jnp.float32)
        if cfg.is_encdec:
            T = enc_len or cfg.encoder_seq_len
            layers["cross_k"] = jnp.zeros((L, batch, cfg.num_kv_heads, T, hd),
                                          self.dtype)
            layers["cross_v"] = jnp.zeros((L, batch, cfg.num_kv_heads, T, hd),
                                          self.dtype)
        cache["layers"] = layers
        return cache

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                cache_len: int) -> Tuple[jax.Array, Cache]:
        """Process the prompt; return (last-position logits, filled cache).

        The returned cache arrays are sized to the prompt; serving pads them
        into a ``cache_len`` decode cache (see serving/engine.py).
        """
        cfg = self.cfg
        x = self._decoder_input(params, batch)
        B, S, _ = x.shape
        if cfg.is_encdec:
            mem = self._encode(params, batch["frames"])
            y, layers = tfm.encdec_decoder_full(params["blocks"], x, mem, cfg,
                                                with_cache=True)
            pad = cache_len - S
            layers["k"] = jnp.pad(layers["k"],
                                  ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
            layers["v"] = jnp.pad(layers["v"],
                                  ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        else:
            y, layers = tfm.stack_prefill(params["blocks"], x, cfg,
                                          self._flags())
            if "k" in layers:
                pad = cache_len - S
                layers["k"] = jnp.pad(layers["k"],
                                      ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
                layers["v"] = jnp.pad(layers["v"],
                                      ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        if self.kv_quant and "k" in layers:
            from repro.models.kvquant import quantize
            layers["k"], layers["k_scale"] = quantize(
                layers["k"], scale_dtype=self.dtype)
            layers["v"], layers["v_scale"] = quantize(
                layers["v"], scale_dtype=self.dtype)
        logits = self._logits(params, y[:, -1:])
        cache = {"pos": jnp.full((B,), S, jnp.int32), "layers": layers}
        return logits, cache

    def decode_step(self, params: Params, tokens: jax.Array, cache: Cache
                    ) -> Tuple[jax.Array, Cache]:
        """One decode step. tokens: [B, 1] -> (logits [B,1,V], new cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        pos = cache["pos"]
        if cfg.is_encdec:
            # absolute sinusoidal position for each row's new token
            table = sinusoidal_positions(cache["layers"]["k"].shape[3],
                                         cfg.d_model)
            B = tokens.shape[0]
            posv = jnp.broadcast_to(jnp.asarray(pos), (B,))
            x = x + table[posv][:, None].astype(x.dtype)
            y, layers = tfm.encdec_decoder_decode(params["blocks"], x,
                                                  cache["layers"], pos, cfg)
        else:
            y, layers = tfm.stack_decode(params["blocks"], x, cache["layers"],
                                         pos, cfg, self._flags())
        logits = self._logits(params, y)
        return logits, {"pos": pos + 1, "layers": layers}

    # ------------------------------------------------------------------
    # dry-run input specs (ShapeDtypeStruct stand-ins; no allocation)
    # ------------------------------------------------------------------
    def input_specs(self, shape: InputShape) -> Dict[str, Any]:
        """Abstract inputs for the step selected by ``shape.kind``."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        f = self.dtype

        def tok(b, s):
            return jax.ShapeDtypeStruct((b, s), i32)

        if shape.kind == "train":
            specs: Dict[str, Any] = {}
            s_text = S - cfg.num_patch_tokens if cfg.arch_type == "vlm" else S
            specs["tokens"] = tok(B, s_text)
            specs["labels"] = tok(B, s_text if cfg.arch_type == "vlm" else S)
            if cfg.arch_type == "vlm":
                specs["patch_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_patch_tokens, cfg.d_model), f)
            if cfg.is_encdec:
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq_len, cfg.d_model), f)
            return specs
        if shape.kind == "prefill":
            specs = {}
            s_text = S - cfg.num_patch_tokens if cfg.arch_type == "vlm" else S
            specs["tokens"] = tok(B, s_text)
            if cfg.arch_type == "vlm":
                specs["patch_embeds"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_patch_tokens, cfg.d_model), f)
            if cfg.is_encdec:
                specs["frames"] = jax.ShapeDtypeStruct(
                    (B, cfg.encoder_seq_len, cfg.d_model), f)
            return specs
        # decode: one token against a cache holding ``seq_len`` positions
        cache = jax.eval_shape(
            lambda: self.init_cache(B, S,
                                    enc_len=cfg.encoder_seq_len or None))
        return {"tokens": tok(B, 1), "cache": cache}

"""Stable Diffusion 1.5 txt2img (SURVEY.md §2 C4, §3e; BASELINE.json
config 5) — the multi-step, large-activation generative family.

TPU-first shaping decisions (SURVEY.md §3e):
- **The entire N-step denoise loop is ONE device-resident executable**: text
  encode (cond + uncond), ``lax.fori_loop`` over DDIM steps with
  classifier-free guidance, VAE decode, and uint8 image quantization all live
  inside a single jitted ``forward``. Exactly two host<->device crossings per
  batch: token ids + seeds in, finished uint8 images out. No per-step Python,
  no per-step dispatch — the main idiomatic divergence from a host-side
  denoise loop.
- Classifier-free guidance runs uncond/cond as one 2B-batch UNet call, so the
  MXU sees one large matmul stream instead of two half-sized ones.
- The DDIM schedule (timesteps, alpha products) is precomputed in numpy at
  build time and baked into the executable as constants — no schedule math on
  device, no dynamic indexing beyond a static-length gather.
- Determinism: requests carry an optional seed; latents come from
  ``jax.random.fold_in(key, seed)`` per item, so identical (prompt, seed)
  requests produce identical images across processes and batch compositions.
- bf16 convs/matmuls, f32 GroupNorm/softmax/scheduler math.

Architecture (SD 1.5 shapes, all overridable via ``cfg.options`` so tests run
a tiny variant on CPU): CLIP ViT-L/14 text tower (12 layers, d=768, causal,
quick-gelu), UNet 860M (320ch, mults 1/2/4/4, 2 res blocks/level, spatial
transformers with one cross-attn block at the three highest resolutions,
8 heads), VAE decoder (128ch base, mults 1/2/4/4, mid self-attention,
latent scale 0.18215). Tokenization reuses the WordPiece machinery from
``tpuserve.text`` with BOS/EOS framing and fixed length 77 — no pretrained
BPE artifacts exist in this container (SURVEY.md §0.1), and with seeded
random weights the tokenizer only needs to be deterministic, not CLIP-BPE
compatible. Real artifacts: ``options["bpe_vocab"]``/``["bpe_merges"]``
load CLIP's byte-level BPE (tpuserve.text.CLIPBPETokenizer);
``options["vocab_file"]`` swaps in a WordPiece vocabulary.
"""

from __future__ import annotations

import io
import json
import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpuserve import frame as frame_wire
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import GenerativeModel
from tpuserve.text import CLIPBPETokenizer, WordPieceTokenizer, synthetic_vocab

MAX_TOKENS = 77  # CLIP text context length; SD conditions on all 77 states.


def _gn(ch: int, name: str, eps: float = 1e-6) -> nn.GroupNorm:
    """GroupNorm(32) with a group count that divides tiny test channels.

    Epsilons follow the published SD modules exactly (torch-import parity):
    1e-5 in UNet ResBlocks and the UNet output norm, 1e-6 in spatial
    transformers and everywhere in the VAE."""
    return nn.GroupNorm(num_groups=math.gcd(32, ch), epsilon=eps,
                        dtype=jnp.float32, name=name)


def _ln(name: str) -> nn.LayerNorm:
    """LayerNorm with torch's default eps 1e-5 (CLIP/transformer blocks use
    torch nn.LayerNorm; flax's 1e-6 default would drift imported weights)."""
    return nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name=name)


def quick_gelu(x):
    return x * jax.nn.sigmoid(1.702 * x)


# -- CLIP text encoder --------------------------------------------------------

class CLIPBlock(nn.Module):
    heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, causal_mask):
        d = x.shape[-1]
        h = _ln("ln1")(x).astype(self.dtype)
        h = nn.MultiHeadDotProductAttention(
            num_heads=self.heads, dtype=self.dtype, deterministic=True,
            name="attn")(h, h, h, mask=causal_mask)
        x = x + h
        h = _ln("ln2")(x).astype(self.dtype)
        h = nn.Dense(4 * d, dtype=self.dtype, name="mlp_up")(h)
        h = quick_gelu(h)
        return x + nn.Dense(d, dtype=self.dtype, name="mlp_down")(h)


class CLIPTextEncoder(nn.Module):
    """CLIP ViT-L/14 text tower: pre-LN causal transformer over 77 tokens;
    SD conditions on the full final hidden-state sequence."""

    vocab_size: int
    layers: int = 12
    d_model: int = 768
    heads: int = 12
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):  # (B, 77) int32 -> (B, 77, d)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     name="token_embed")(ids)
        pos = self.param("pos_embed", nn.initializers.normal(0.01),
                         (MAX_TOKENS, self.d_model))
        x = x + pos[None, : ids.shape[1], :].astype(self.dtype)
        mask = nn.make_causal_mask(ids)
        for i in range(self.layers):
            x = CLIPBlock(self.heads, dtype=self.dtype, name=f"layer{i}")(x, mask)
        return _ln("ln_final")(x).astype(self.dtype)


# -- UNet ----------------------------------------------------------------------

def timestep_embedding(t: jax.Array, dim: int) -> jax.Array:
    """Sinusoidal embedding, f32: (B,) int -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


class ResBlock(nn.Module):
    out_ch: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, temb):  # x (B,H,W,C), temb (B,T)
        h = nn.swish(_gn(x.shape[-1], "norm1", eps=1e-5)(x)).astype(self.dtype)
        h = nn.Conv(self.out_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv1")(h)
        t = nn.Dense(self.out_ch, dtype=self.dtype, name="temb_proj")(
            nn.swish(temb).astype(self.dtype))
        h = h + t[:, None, None, :]
        h = nn.swish(_gn(self.out_ch, "norm2", eps=1e-5)(h)).astype(self.dtype)
        h = nn.Conv(self.out_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv2")(h)
        if x.shape[-1] != self.out_ch:
            x = nn.Conv(self.out_ch, (1, 1), dtype=self.dtype, name="skip")(x)
        return x + h


class TransformerBlock(nn.Module):
    """LN->self-attn, LN->cross-attn(text), LN->GEGLU feed-forward."""

    heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, ctx):  # x (B,N,C), ctx (B,77,Dtxt)
        d = x.shape[-1]

        def attn(name: str):
            return nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype, deterministic=True,
                name=name)

        h = _ln("ln1")(x).astype(self.dtype)
        x = x + attn("self_attn")(h, h, h)
        h = _ln("ln2")(x).astype(self.dtype)
        x = x + attn("cross_attn")(h, ctx, ctx)
        h = _ln("ln3")(x).astype(self.dtype)
        up = nn.Dense(8 * d, dtype=self.dtype, name="ff_up")(h)
        gate, val = jnp.split(up, 2, axis=-1)
        return x + nn.Dense(d, dtype=self.dtype, name="ff_down")(
            val * nn.gelu(gate, approximate=False))


class SpatialTransformer(nn.Module):
    heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, ctx):  # (B,H,W,C)
        b, hh, ww, c = x.shape
        h = _gn(c, "norm")(x).astype(self.dtype)
        h = nn.Conv(c, (1, 1), dtype=self.dtype, name="proj_in")(h)
        h = h.reshape(b, hh * ww, c)
        h = TransformerBlock(self.heads, dtype=self.dtype,
                             name="block")(h, ctx)
        h = h.reshape(b, hh, ww, c)
        return x + nn.Conv(c, (1, 1), dtype=self.dtype, name="proj_out")(h)


class UNet(nn.Module):
    """SD 1.5 epsilon-predictor: 4ch latent in/out, cross-attended on text."""

    model_ch: int = 320
    mults: Sequence[int] = (1, 2, 4, 4)
    num_res: int = 2
    attn_levels: Sequence[int] = (0, 1, 2)
    heads: int = 8
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, t, ctx):  # x (B,h,w,4), t (B,), ctx (B,77,D)
        temb = timestep_embedding(t, self.model_ch)
        temb = nn.Dense(4 * self.model_ch, dtype=self.dtype, name="time1")(
            temb.astype(self.dtype))
        temb = nn.Dense(4 * self.model_ch, dtype=self.dtype, name="time2")(
            nn.swish(temb))

        h = nn.Conv(self.model_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv_in")(x)
        skips = [h]
        # Down path.
        for i, m in enumerate(self.mults):
            for j in range(self.num_res):
                h = ResBlock(self.model_ch * m, dtype=self.dtype,
                             name=f"down{i}_res{j}")(h, temb)
                if i in self.attn_levels:
                    h = SpatialTransformer(self.heads, dtype=self.dtype,
                                           name=f"down{i}_attn{j}")(h, ctx)
                skips.append(h)
            if i != len(self.mults) - 1:
                # Explicit (1,1) padding, not SAME: with stride 2, SAME pads
                # (0,1) while SD's Downsample pads symmetrically — same output
                # shape, different window alignment (caught by torch parity).
                h = nn.Conv(h.shape[-1], (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)),
                            dtype=self.dtype, name=f"down{i}_ds")(h)
                skips.append(h)
        # Middle.
        h = ResBlock(h.shape[-1], dtype=self.dtype, name="mid_res1")(h, temb)
        h = SpatialTransformer(self.heads, dtype=self.dtype,
                               name="mid_attn")(h, ctx)
        h = ResBlock(h.shape[-1], dtype=self.dtype, name="mid_res2")(h, temb)
        # Up path.
        for i, m in reversed(list(enumerate(self.mults))):
            for j in range(self.num_res + 1):
                h = jnp.concatenate([h, skips.pop()], axis=-1)
                h = ResBlock(self.model_ch * m, dtype=self.dtype,
                             name=f"up{i}_res{j}")(h, temb)
                if i in self.attn_levels:
                    h = SpatialTransformer(self.heads, dtype=self.dtype,
                                           name=f"up{i}_attn{j}")(h, ctx)
            if i != 0:
                b, hh, ww, c = h.shape
                h = jax.image.resize(h, (b, hh * 2, ww * 2, c), method="nearest")
                h = nn.Conv(c, (3, 3), padding="SAME", dtype=self.dtype,
                            name=f"up{i}_us")(h)
        h = nn.swish(_gn(h.shape[-1], "norm_out", eps=1e-5)(h)).astype(self.dtype)
        return nn.Conv(4, (3, 3), padding="SAME", dtype=jnp.float32,
                       name="conv_out")(h)


# -- VAE decoder ---------------------------------------------------------------

class VAEAttn(nn.Module):
    """Single-head full self-attention over spatial positions (VAE mid)."""

    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, hh, ww, c = x.shape
        h = _gn(c, "norm")(x).astype(self.dtype)
        q = nn.Dense(c, dtype=self.dtype, name="q")(h).reshape(b, hh * ww, c)
        k = nn.Dense(c, dtype=self.dtype, name="k")(h).reshape(b, hh * ww, c)
        v = nn.Dense(c, dtype=self.dtype, name="v")(h).reshape(b, hh * ww, c)
        s = jnp.einsum("bqc,bkc->bqk", q, k).astype(jnp.float32) * (c ** -0.5)
        a = jax.nn.softmax(s, axis=-1).astype(self.dtype)
        h = jnp.einsum("bqk,bkc->bqc", a, v).reshape(b, hh, ww, c)
        return x + nn.Dense(c, dtype=self.dtype, name="proj")(h)


class VAEResBlock(nn.Module):
    out_ch: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.swish(_gn(x.shape[-1], "norm1")(x)).astype(self.dtype)
        h = nn.Conv(self.out_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv1")(h)
        h = nn.swish(_gn(self.out_ch, "norm2")(h)).astype(self.dtype)
        h = nn.Conv(self.out_ch, (3, 3), padding="SAME", dtype=self.dtype,
                    name="conv2")(h)
        if x.shape[-1] != self.out_ch:
            x = nn.Conv(self.out_ch, (1, 1), dtype=self.dtype, name="skip")(x)
        return x + h


class VAEDecoder(nn.Module):
    """AutoencoderKL decoder: (B,h,w,4) latents -> (B,8h,8w,3) in [-1,1]."""

    ch: int = 128
    mults: Sequence[int] = (1, 2, 4, 4)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, z):
        z = nn.Conv(z.shape[-1], (1, 1), dtype=self.dtype, name="post_quant")(z)
        top = self.ch * self.mults[-1]
        h = nn.Conv(top, (3, 3), padding="SAME", dtype=self.dtype, name="conv_in")(z)
        h = VAEResBlock(top, dtype=self.dtype, name="mid_res1")(h)
        h = VAEAttn(dtype=self.dtype, name="mid_attn")(h)
        h = VAEResBlock(top, dtype=self.dtype, name="mid_res2")(h)
        for i, m in reversed(list(enumerate(self.mults))):
            for j in range(3):
                h = VAEResBlock(self.ch * m, dtype=self.dtype,
                                name=f"up{i}_res{j}")(h)
            if i != 0:
                b, hh, ww, c = h.shape
                h = jax.image.resize(h, (b, hh * 2, ww * 2, c), method="nearest")
                h = nn.Conv(c, (3, 3), padding="SAME", dtype=self.dtype,
                            name=f"up{i}_us")(h)
        h = nn.swish(_gn(h.shape[-1], "norm_out")(h)).astype(self.dtype)
        return nn.Conv(3, (3, 3), padding="SAME", dtype=jnp.float32,
                       name="conv_out")(h)


# -- DDIM schedule (host-side numpy, baked as executable constants) -----------

def ddim_schedule(steps: int, train_steps: int = 1000,
                  beta_start: float = 0.00085, beta_end: float = 0.012):
    """SD's scaled-linear schedule -> per-step (t, alpha_t, alpha_prev) arrays
    of static length `steps`, ordered from t=high noise down to 0."""
    betas = np.linspace(beta_start ** 0.5, beta_end ** 0.5, train_steps,
                        dtype=np.float64) ** 2
    acum = np.cumprod(1.0 - betas)
    ts = np.linspace(0, train_steps - 1, steps).round().astype(np.int64)[::-1]
    a_t = acum[ts]
    a_prev = np.concatenate([acum[ts[1:]], [1.0]])
    return (ts.astype(np.int32), a_t.astype(np.float32),
            a_prev.astype(np.float32))


# -- serving -------------------------------------------------------------------

class SD15Serving(GenerativeModel):
    """txt2img over HTTP: JSON {"prompt", "negative_prompt"?, "seed"?} in,
    PNG bytes out. The negative prompt rides the classifier-free-guidance
    uncond lane (empty prompt when unset), steering generation away from it.

    Two serving shapes (both deterministic in (prompt, negative, seed)):
    the one-shot ``forward`` bakes the whole N-step denoise loop into one
    executable (the static batcher's locked-batch path), and the
    GenerativeModel decomposition serves the SAME math through the
    iteration-level engine — ``init_state`` text-encodes + seeds latents,
    each ``step`` is one DDIM iteration over the slot block (per-slot step
    counters, so freshly folded-in requests denoise beside half-finished
    ones), and ``extract`` runs the VAE decode only when a slot finishes.
    Fixed ``steps`` per request keeps the large-activation path static."""

    def __init__(self, cfg: ModelConfig) -> None:
        super().__init__(cfg)
        o = cfg.options
        self.dtype = jnp.dtype(cfg.dtype)
        self.steps = int(o.get("steps", 20))
        self.guidance = float(o.get("guidance", 7.5))
        # Streamed responses emit a decoded preview image every N denoise
        # steps (0 disables). Each preview reuses the compiled extract
        # program — previews never add a compile, only extract invocations.
        self.preview_every = int(o.get("preview_every", 0))
        if self.preview_every < 0:
            raise ValueError(
                f"options.preview_every must be >= 0, got {self.preview_every}")
        # The VAE upsamples 2x per level past the first, so the latent edge
        # must be image_size / 2^(levels-1) for the PNG to match image_size
        # (8x for the standard 4-level SD VAE).
        vae_mults = tuple(o.get("vae_mults", (1, 2, 4, 4)))
        self.latent = cfg.image_size // (2 ** (len(vae_mults) - 1))
        vocab_file = o.get("vocab_file")
        if bool(o.get("bpe_vocab")) != bool(o.get("bpe_merges")):
            raise ValueError(
                "bpe_vocab and bpe_merges must be set together "
                "(CLIP BPE needs vocab.json + merges.txt)")
        if o.get("bpe_vocab"):
            # Real SD/CLIP artifacts: byte-level BPE (vocab.json + merges.txt).
            self.tokenizer = CLIPBPETokenizer(o["bpe_vocab"], o["bpe_merges"])
        elif vocab_file:
            self.tokenizer = WordPieceTokenizer.from_vocab_file(vocab_file)
        else:
            self.tokenizer = WordPieceTokenizer(
                synthetic_vocab(int(o.get("vocab_size", 8192))))
        vocab_size = max(self.tokenizer.vocab.values()) + 1
        self.text_encoder = CLIPTextEncoder(
            vocab_size=vocab_size,
            layers=int(o.get("text_layers", 12)),
            d_model=int(o.get("text_d_model", 768)),
            heads=int(o.get("text_heads", 12)),
            dtype=self.dtype)
        self.unet = UNet(
            model_ch=int(o.get("unet_ch", 320)),
            mults=tuple(o.get("unet_mults", (1, 2, 4, 4))),
            num_res=int(o.get("unet_res", 2)),
            attn_levels=tuple(o.get("unet_attn_levels", (0, 1, 2))),
            heads=int(o.get("unet_heads", 8)),
            dtype=self.dtype)
        self.vae = VAEDecoder(
            ch=int(o.get("vae_ch", 128)),
            mults=tuple(o.get("vae_mults", (1, 2, 4, 4))),
            dtype=self.dtype)
        self.schedule = ddim_schedule(self.steps)

    # -- params ---------------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Any:
        k1, k2, k3 = jax.random.split(rng, 3)
        ids = jnp.zeros((1, MAX_TOKENS), jnp.int32)
        lat = jnp.zeros((1, self.latent, self.latent, 4), jnp.float32)
        t = jnp.zeros((1,), jnp.int32)
        ctx = jnp.zeros((1, MAX_TOKENS, self.text_encoder.d_model), self.dtype)
        return {
            "text": self.text_encoder.init(k1, ids),
            "unet": self.unet.init(k2, lat, t, ctx),
            "vae": self.vae.init(k3, lat),
        }

    def import_torch_variables(self, flat: dict) -> Any:
        """Published SD 1.5 single-file checkpoint (LDM layout, safetensors
        or .ckpt) -> our param tree; see tpuserve.models.sd15_import. Pair
        with options bpe_vocab/bpe_merges for the real CLIP tokenizer."""
        from tpuserve.models.sd15_import import import_ldm_checkpoint

        return import_ldm_checkpoint(self, flat)

    # -- shapes ---------------------------------------------------------------
    def input_signature(self, bucket: tuple) -> Any:
        (b,) = bucket
        return (
            jax.ShapeDtypeStruct((b, MAX_TOKENS), jnp.int32),
            jax.ShapeDtypeStruct((b, MAX_TOKENS), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        )

    # -- device side ----------------------------------------------------------
    def forward(self, params: Any, batch: Any) -> dict:
        ids, neg_ids, seeds = batch
        b = ids.shape[0]
        # One 2B text-encoder call covers cond + per-item uncond: negative
        # prompts make the uncond row per-request (empty prompt when unset),
        # and the text tower is a rounding error next to `steps` UNet calls.
        ctx2 = self.text_encoder.apply(
            params["text"], jnp.concatenate([neg_ids, ids], axis=0))  # (2B, 77, D)

        keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.key(0), s))(seeds)
        lat = jax.vmap(lambda k: jax.random.normal(
            k, (self.latent, self.latent, 4), jnp.float32))(keys)

        ts, a_t, a_prev = (jnp.asarray(x) for x in self.schedule)
        g = jnp.float32(self.guidance)

        def body(i, lat):
            t = jnp.broadcast_to(ts[i], (2 * b,))
            x2 = jnp.concatenate([lat, lat], axis=0)
            eps2 = self.unet.apply(params["unet"], x2, t, ctx2)
            eps_u, eps_c = jnp.split(eps2, 2, axis=0)
            eps = eps_u + g * (eps_c - eps_u)
            at, ap = a_t[i], a_prev[i]
            x0 = (lat - jnp.sqrt(1.0 - at) * eps) / jnp.sqrt(at)
            return jnp.sqrt(ap) * x0 + jnp.sqrt(1.0 - ap) * eps

        lat = jax.lax.fori_loop(0, self.steps, body, lat)
        img = self.vae.apply(params["vae"], lat / 0.18215)
        img = jnp.clip((img + 1.0) * 127.5, 0.0, 255.0).astype(jnp.uint8)
        return {"image": img}

    # -- engine decomposition (tpuserve.genserve) -------------------------------
    def state_signature(self, slots: int) -> Any:
        return {
            "lat": jax.ShapeDtypeStruct(
                (slots, self.latent, self.latent, 4), jnp.float32),
            "ctx": jax.ShapeDtypeStruct(
                (slots, 2, MAX_TOKENS, self.text_encoder.d_model), self.dtype),
            "step_i": jax.ShapeDtypeStruct((slots,), jnp.int32),
            "done": jax.ShapeDtypeStruct((slots,), jnp.bool_),
        }

    def gen_item_signature(self) -> Any:
        return (
            jax.ShapeDtypeStruct((MAX_TOKENS,), jnp.int32),
            jax.ShapeDtypeStruct((MAX_TOKENS,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )

    def init_state(self, params: Any, item: Any) -> Any:
        """Once-per-request work: text-encode cond + uncond, seed the
        latent. Same math as forward's prologue, per slot."""
        ids, neg_ids, seed = item
        ctx2 = self.text_encoder.apply(
            params["text"], jnp.stack([neg_ids, ids]))  # (2, 77, D)
        key = jax.random.fold_in(jax.random.key(0), seed)
        lat = jax.random.normal(
            key, (self.latent, self.latent, 4), jnp.float32)
        return {"lat": lat, "ctx": ctx2.astype(self.dtype),
                "step_i": jnp.int32(0), "done": jnp.bool_(False)}

    def step(self, params: Any, state: Any) -> tuple[Any, dict]:
        """One DDIM iteration over the whole slot block, each slot at its
        OWN schedule index — a request folded in at iteration 400 of the
        block's life denoises from its own t=high-noise next to slots
        about to finish. Finished/free slots freeze via ``done``."""
        lat, ctx, step_i, done = (state["lat"], state["ctx"],
                                  state["step_i"], state["done"])
        b = lat.shape[0]
        ts, a_t, a_prev = (jnp.asarray(x) for x in self.schedule)
        g = jnp.float32(self.guidance)
        idx = jnp.clip(step_i, 0, self.steps - 1)
        t2 = jnp.concatenate([ts[idx], ts[idx]], axis=0)  # (2B,)
        x2 = jnp.concatenate([lat, lat], axis=0)
        ctx2 = jnp.concatenate([ctx[:, 0], ctx[:, 1]], axis=0)  # (2B, 77, D)
        eps2 = self.unet.apply(params["unet"], x2, t2, ctx2)
        eps_u, eps_c = jnp.split(eps2, 2, axis=0)
        eps = eps_u + g * (eps_c - eps_u)
        at = a_t[idx][:, None, None, None]
        ap = a_prev[idx][:, None, None, None]
        x0 = (lat - jnp.sqrt(1.0 - at) * eps) / jnp.sqrt(at)
        new_lat = jnp.sqrt(ap) * x0 + jnp.sqrt(1.0 - ap) * eps
        lat2 = jnp.where(done[:, None, None, None], lat, new_lat)
        step2 = jnp.where(done, step_i, step_i + 1)
        done2 = step2 >= self.steps
        return ({"lat": lat2, "ctx": ctx, "step_i": step2, "done": done2},
                {"done": done2, "step_i": step2})

    def extract(self, params: Any, state: Any, slot: Any) -> Any:
        """The tail work runs ONCE per finished slot: VAE decode + uint8
        quantization of that slot's latent only."""
        lat = jax.lax.dynamic_index_in_dim(state["lat"], slot, 0)  # (1,h,w,4)
        img = self.vae.apply(params["vae"], lat / 0.18215)
        img = jnp.clip((img + 1.0) * 127.5, 0.0, 255.0).astype(jnp.uint8)
        return {"image": img[0]}

    def gen_max_steps(self) -> int:
        return self.steps

    def finalize(self, extracted: Any, item: Any) -> bytes:
        return self._png(np.asarray(extracted["image"]))

    # -- streaming (ISSUE 17) ---------------------------------------------------
    # sd15 streams over the chunked binary frame wire: KIND_EVENT frames
    # carry progress/done/error JSON, single-item KIND_RGB8 frames carry
    # previews and the final image. Everything except the final image and
    # the terminal is droppable — a slow reader loses progress, never art.
    def stream_units(self, step_out: dict, slot: int, stream: dict) -> list:
        s = int(step_out["step_i"][slot])
        sent = int(stream.get("sent", 0))
        if s <= sent:
            return []
        stream["sent"] = s
        return [{"type": "progress", "step": i, "steps": self.steps,
                 "droppable": True} for i in range(sent + 1, s + 1)]

    def stream_wants_preview(self, step_out: dict, slot: int,
                             stream: dict) -> bool:
        if not self.preview_every or bool(step_out["done"][slot]):
            return False
        s = int(step_out["step_i"][slot])
        return s - int(stream.get("previewed", 0)) >= self.preview_every

    def stream_preview_unit(self, extracted: Any, stream: dict) -> dict:
        stream["previewed"] = int(stream.get("sent", 0))
        return {"type": "preview", "image": np.asarray(extracted["image"]),
                "droppable": True}

    def stream_final_units(self, extracted: Any, result: Any) -> list:
        return ([{"type": "image", "image": np.asarray(extracted["image"])}]
                + super().stream_final_units(extracted, result))

    def stream_usage(self, result: Any) -> dict:
        return {"images": 1}

    def stream_content_type(self) -> str:
        return frame_wire.CONTENT_TYPE

    def encode_stream_unit(self, unit: dict) -> bytes:
        if unit["type"] in ("image", "preview"):
            return frame_wire.encode_frame(
                [unit["image"]], frame_wire.KIND_RGB8, self.cfg.image_size)
        data = {k: v for k, v in unit.items() if k != "droppable"}
        return frame_wire.encode_stream_event(
            json.dumps(data).encode("utf-8"))

    def stream_heartbeat(self) -> bytes:
        return frame_wire.encode_stream_event(b'{"type": "hb"}')

    # -- host side --------------------------------------------------------------
    def _tokenize(self, prompt: str) -> np.ndarray:
        """Prompt -> fixed (77,) int32: BOS + pieces + EOS, pad-id padded."""
        ids, _ = self.tokenizer.encode(prompt, MAX_TOKENS)
        return ids

    def host_decode(self, payload: bytes, content_type: str) -> Any:
        if content_type.startswith("application/json"):
            body = json.loads(payload.decode("utf-8"))
            prompt = body.get("prompt")
            if not isinstance(prompt, str):
                raise ValueError('JSON body must contain "prompt": str')
            negative = body.get("negative_prompt", "")
            if not isinstance(negative, str):
                raise ValueError('"negative_prompt" must be a string')
            seed = int(body.get("seed", 0))
        else:
            prompt, negative, seed = payload.decode("utf-8"), "", 0
        # The negative prompt rides the classifier-free-guidance uncond lane
        # (empty prompt when unset), steering generation AWAY from it.
        return self._tokenize(prompt), self._tokenize(negative), np.int32(seed)

    def canary_item(self) -> Any:
        return self.host_decode(b'{"prompt": "canary", "seed": 1}',
                                "application/json")

    @staticmethod
    def _png(arr: np.ndarray) -> bytes:
        from PIL import Image

        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, "PNG")
        return buf.getvalue()

    def host_postprocess(self, outputs: dict, n_valid: int) -> list[bytes]:
        return [self._png(np.asarray(outputs["image"][r]))
                for r in range(n_valid)]

    # -- parallelism ------------------------------------------------------------
    def partition_rules(self) -> list[tuple[str, P]]:
        if self.cfg.tp <= 1:
            return [(".*", P())]
        return [
            # UNet/CLIP attention: shard heads; GEGLU/MLP: shard hidden.
            (r"(self_attn|cross_attn|attn)/(query|key|value)/kernel", P(None, "model", None)),
            (r"(self_attn|cross_attn|attn)/out/kernel", P("model", None, None)),
            (r"(ff_up|mlp_up)/kernel", P(None, "model")),
            (r"(ff_down|mlp_down)/kernel", P("model", None)),
            (r".*", P()),
        ]


def create(cfg: ModelConfig) -> SD15Serving:
    return SD15Serving(cfg)

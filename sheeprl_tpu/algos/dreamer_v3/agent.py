"""DreamerV3 agent — flax modules, functional player, Hafner init.

Behavioral contract from the reference ``sheeprl/algos/dreamer_v3/agent.py``
(CNNEncoder :30, MLPEncoder :85, CNN/MLPDecoder :138-259, RecurrentModel :262,
RSSM :314-457, PlayerDV3 :460-585, Actor :588-767, build_models :900-1144).

TPU-native design (NOT a translation):

- The RSSM exposes *single-step* methods (``dynamic``, ``imagination``); the
  time loop lives in the train step as ``jax.lax.scan`` so XLA fuses the whole
  sequence into one program instead of T Python GRU steps
  (reference dreamer_v3.py:121-133 — SURVEY.md "hard parts" #1).
- The stateful ``PlayerDV3`` (mutates ``self.recurrent_state`` etc.,
  reference agent.py:516-537) becomes an explicit ``(actions, recurrent,
  stochastic)`` pytree threaded through pure jitted functions with
  ``jnp.where`` masking for per-env resets.
- Hafner initialization (reference utils.py init_weights/uniform_init_weights
  + build_models :1109-1119) is a pure transform over the freshly-initialized
  param pytree — truncated-normal for every kernel, uniform/zero overrides for
  the named output heads.
- Distributions are built *inside* jit from raw head outputs; sampling takes
  explicit PRNG keys.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.norm import FastLayerNorm

from sheeprl_tpu.distributions import (
    Bernoulli,
    Independent,
    Normal,
    OneHotCategorical,
    OneHotCategoricalStraightThrough,
    TanhNormal,
    TruncatedNormal,
)
from sheeprl_tpu.models import MLP, CNN, DeCNN, LayerNormGRUCell, resolve_activation

sg = jax.lax.stop_gradient


# ---------------------------------------------------------------------------
# encoders / decoders
# ---------------------------------------------------------------------------


class CNNEncoder(nn.Module):
    """Image encoder (reference agent.py:30-82): ``stages`` conv blocks of
    k=4/s=2/p=1 with channels ``[1,2,4,...]×multiplier``, channel-last
    LayerNorm (free in NHWC), SiLU, then flatten. Input ``[..., C, H, W]``."""

    keys: Sequence[str]
    channels_multiplier: int
    stages: int = 4
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, obs: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        x = CNN(
            channels=[(2**i) * self.channels_multiplier for i in range(self.stages)],
            kernel_sizes=4,
            strides=2,
            paddings=1,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            flatten=True,
            dtype=self.dtype,
        )(x)
        return x


class MLPEncoder(nn.Module):
    """Vector encoder (reference agent.py:85-135): symlog inputs, N dense
    blocks with LayerNorm+SiLU."""

    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 512
    layer_norm: bool = True
    activation: Any = "silu"
    symlog_inputs: bool = True
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, obs: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        return MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            symlog_inputs=self.symlog_inputs,
            dtype=self.dtype,
        )(x)


class MultiEncoderDV3(nn.Module):
    """Concat of the cnn and mlp encoders' features (reference wraps both in a
    MultiEncoder; same semantics)."""

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    channels_multiplier: int
    stages: int
    mlp_layers: int
    dense_units: int
    layer_norm: bool = True
    cnn_act: Any = "silu"
    dense_act: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, obs: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        feats = []
        if self.cnn_keys:
            feats.append(
                CNNEncoder(
                    keys=self.cnn_keys,
                    channels_multiplier=self.channels_multiplier,
                    stages=self.stages,
                    layer_norm=self.layer_norm,
                    activation=self.cnn_act,
                    dtype=self.dtype,
                    name="cnn_encoder",
                )(obs)
            )
        if self.mlp_keys:
            feats.append(
                MLPEncoder(
                    keys=self.mlp_keys,
                    mlp_layers=self.mlp_layers,
                    dense_units=self.dense_units,
                    layer_norm=self.layer_norm,
                    activation=self.dense_act,
                    dtype=self.dtype,
                    name="mlp_encoder",
                )(obs)
            )
        return jnp.concatenate(feats, axis=-1) if len(feats) > 1 else feats[0]

    @staticmethod
    def output_width(
        cnn_keys: Sequence[str],
        mlp_keys: Sequence[str],
        image_size: Tuple[int, int],
        channels_multiplier: int,
        stages: int,
        dense_units: int,
    ) -> int:
        """Static feature width: CNN flatten (k=4/s=2/p=1 halves each stage,
        channels double from ``channels_multiplier``) + MLP ``dense_units``."""
        width = 0
        if cnn_keys:
            h, w = image_size[0] >> stages, image_size[1] >> stages
            width += h * w * channels_multiplier * 2 ** (stages - 1)
        if mlp_keys:
            width += dense_units
        return width


class CNNDecoder(nn.Module):
    """Pixel decoder (reference agent.py:138-211): Linear projection to the
    encoder's 4×4 feature map, then transposed-conv stages back to the image;
    output shifted by +0.5. The final conv keeps bias and gets the
    uniform-head init (name ``head``)."""

    output_channels: Sequence[int]
    channels_multiplier: int
    stages: int
    image_size: Tuple[int, int]
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, latent: jnp.ndarray) -> jnp.ndarray:
        total_c = sum(self.output_channels)
        top_c = (2 ** (self.stages - 1)) * self.channels_multiplier
        base = self.image_size[0] // (2**self.stages)
        x = nn.Dense(top_c * base * base, dtype=self.dtype)(latent)
        lead = x.shape[:-1]
        x = jnp.reshape(x, lead + (top_c, base, base))
        hidden = [
            (2**i) * self.channels_multiplier for i in reversed(range(self.stages - 1))
        ]
        if hidden:
            x = DeCNN(
                channels=hidden,
                kernel_sizes=4,
                strides=2,
                paddings=1,
                activation=self.activation,
                final_activation=self.activation,
                layer_norm=self.layer_norm,
                norm_eps=1e-3,
                bias=not self.layer_norm,
                dtype=self.dtype,
            )(x)
        x = DeCNN(
            channels=[total_c],
            kernel_sizes=4,
            strides=2,
            paddings=1,
            activation="identity",
            layer_norm=False,
            bias=True,
            dtype=self.dtype,
            name="head",
        )(x)
        # stay in the compute dtype: the conv output is already bf16-limited
        # under mixed precision, so a pixel-space +0.5 in bf16 costs at most
        # ~2^-9 (quarter-pixel) of extra rounding while halving the bytes of
        # the reconstruction tensor and its layout-normalization copy — the
        # MSE loss converts to f32 inside its reduce fusion
        return x + jnp.asarray(0.5, x.dtype)


class MLPDecoder(nn.Module):
    """Vector decoder (reference agent.py:214-259): shared dense trunk,
    one linear head per key (heads get the uniform init, names ``head_<k>``)."""

    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 512
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, latent: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        x = MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            dtype=self.dtype,
        )(latent)
        return {
            k: nn.Dense(dim, dtype=self.dtype, name=f"head_{k}")(x).astype(jnp.float32)
            for k, dim in zip(self.keys, self.output_dims)
        }


# ---------------------------------------------------------------------------
# recurrent model / RSSM
# ---------------------------------------------------------------------------


class RecurrentModel(nn.Module):
    """Dense pre-layer + LayerNorm GRU cell (reference agent.py:262-311)."""

    recurrent_state_size: int
    dense_units: int
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
        feat = MLP(
            hidden_sizes=[self.dense_units],
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            dtype=self.dtype,
        )(x)
        # the carried state stays f32 (the cell's gate mix promotes back)
        return LayerNormGRUCell(
            self.recurrent_state_size, bias=False, layer_norm=True, dtype=self.dtype, name="gru"
        )(feat, h).astype(jnp.float32)


class _StochasticModel(nn.Module):
    """MLP trunk + logits head — shared shape of the transition (prior) and
    representation (posterior) models. The head carries the uniform init."""

    hidden_size: int
    stoch_size: int  # stochastic_size * discrete_size
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = MLP(
            hidden_sizes=[self.hidden_size],
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            dtype=self.dtype,
        )(x)
        # categorical logits in f32: the unimix/log-softmax/KL math is
        # precision-sensitive
        return nn.Dense(self.stoch_size, dtype=self.dtype, name="head")(x).astype(jnp.float32)


class _RepresentationModel(nn.Module):
    """Posterior trunk with the embed half of the first layer split out.

    Mathematically identical to ``_StochasticModel`` over
    ``concat([h, embed])`` — the joint first-layer kernel is stored as ONE
    parameter (same init statistics as the reference's single Linear,
    reference agent.py:406-424) and sliced at apply time — but exposes
    ``project_embed`` so the train step can batch the embed projection over
    the whole ``[T, B]`` sequence *outside* the sequential RSSM scan: the
    embed width (e.g. 4096 from the CNN) dwarfs the recurrent width (512),
    so this removes ~8/9 of the posterior-trunk FLOPs and weight streaming
    from the latency-critical per-timestep path.
    """

    hidden_size: int
    stoch_size: int  # stochastic_size * discrete_size
    h_size: int
    embed_size: int
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    def setup(self):
        self.kernel = self.param(
            "trunk_kernel",
            nn.initializers.lecun_normal(),
            (self.h_size + self.embed_size, self.hidden_size),
        )
        if self.layer_norm:
            self.norm = FastLayerNorm(epsilon=1e-3, dtype=self.dtype, name="trunk_ln")
        else:
            self.bias = self.param(
                "trunk_bias", nn.initializers.zeros_init(), (self.hidden_size,)
            )
        self.head = nn.Dense(self.stoch_size, dtype=self.dtype, name="head")

    def _cast(self, x: jnp.ndarray) -> jnp.ndarray:
        return x.astype(self.dtype) if self.dtype is not None else x

    def project_embed(self, embed: jnp.ndarray) -> jnp.ndarray:
        return self._cast(embed) @ self._cast(self.kernel[self.h_size :])

    def from_projected(self, h: jnp.ndarray, embed_proj: jnp.ndarray) -> jnp.ndarray:
        x = self._cast(h) @ self._cast(self.kernel[: self.h_size]) + self._cast(embed_proj)
        if self.layer_norm:
            x = self.norm(x)
        else:
            x = x + self._cast(self.bias)
        x = resolve_activation(self.activation)(x)
        return self.head(x).astype(jnp.float32)

    def __call__(self, h: jnp.ndarray, embed: jnp.ndarray) -> jnp.ndarray:
        return self.from_projected(h, self.project_embed(embed))


def uniform_mix(logits: jnp.ndarray, discrete: int, unimix: float) -> jnp.ndarray:
    """1% uniform mixture on categorical logits (reference agent.py:392-404).

    ``logits`` is ``[..., S*D]`` flat; returns the same flat shape.
    """
    shape = logits.shape
    logits = jnp.reshape(logits, shape[:-1] + (-1, discrete))
    if unimix > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        probs = (1.0 - unimix) * probs + unimix / discrete
        logits = jnp.log(probs)
    return jnp.reshape(logits, shape)


def compute_stochastic_state(
    logits: jnp.ndarray,
    discrete: int,
    key: Optional[jax.Array],
    sample: bool = True,
    gumbel: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Sample (straight-through) or take the mode of the categorical latent
    (reference dreamer_v2/utils.py:39-58). ``logits`` flat ``[..., S*D]`` →
    state ``[..., S, D]``.

    ``gumbel`` (shape ``[..., S, D]``) is pre-drawn Gumbel(0,1) noise: the
    train scans generate it for the whole sequence in one vectorized draw
    outside the time loop, leaving only an add+argmax on the sequential path
    (``argmax(logits + g)`` is the same sampler ``jax.random.categorical``
    uses, and is invariant to the log-softmax normalization)."""
    shape = logits.shape
    logits = jnp.reshape(logits, shape[:-1] + (-1, discrete))
    if sample and gumbel is not None:
        one = jax.nn.one_hot(
            jnp.argmax(logits + gumbel, axis=-1), discrete, dtype=logits.dtype
        )
        probs = jax.nn.softmax(logits, axis=-1)
        return one + probs - jax.lax.stop_gradient(probs)
    dist = OneHotCategoricalStraightThrough(logits=logits)
    return dist.rsample(key) if sample else dist.mode


class RSSM(nn.Module):
    """Recurrent state-space model (reference agent.py:314-457).

    All methods are single-step over a batch; callers scan them over time.
    The stochastic state is carried *flat* ``[..., S*D]``.
    """

    recurrent_state_size: int
    stochastic_size: int
    discrete_size: int
    dense_units: int
    hidden_size: int
    embed_size: int
    representation_hidden_size: Optional[int] = None
    layer_norm: bool = True
    unimix: float = 0.01
    activation: Any = "silu"
    dtype: Optional[Any] = None

    def setup(self):
        self.recurrent_model = RecurrentModel(
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.dense_units,
            layer_norm=self.layer_norm,
            activation=self.activation,
            dtype=self.dtype,
        )
        stoch = self.stochastic_size * self.discrete_size
        self.representation_model = _RepresentationModel(
            hidden_size=self.representation_hidden_size or self.hidden_size,
            stoch_size=stoch,
            h_size=self.recurrent_state_size,
            embed_size=self.embed_size,
            layer_norm=self.layer_norm,
            activation=self.activation,
            dtype=self.dtype,
        )
        self.transition_model = _StochasticModel(
            hidden_size=self.hidden_size,
            stoch_size=stoch,
            layer_norm=self.layer_norm,
            activation=self.activation,
            dtype=self.dtype,
        )

    def _transition(
        self,
        recurrent_out: jnp.ndarray,
        key: Optional[jax.Array],
        sample_state: bool = True,
        gumbel: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Prior logits + (sampled|mode) prior, flat (reference :426-439)."""
        logits = uniform_mix(self.transition_model(recurrent_out), self.discrete_size, self.unimix)
        state = compute_stochastic_state(
            logits, self.discrete_size, key, sample=sample_state, gumbel=gumbel
        )
        return logits, jnp.reshape(state, state.shape[:-2] + (-1,))

    def _representation(
        self, recurrent_state: jnp.ndarray, embedded_obs: jnp.ndarray, key: jax.Array
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Posterior logits + sampled posterior, flat (reference :406-424)."""
        return self._representation_projected(
            recurrent_state, self.project_embed(embedded_obs), key
        )

    def project_embed(self, embedded_obs: jnp.ndarray) -> jnp.ndarray:
        """Batchable (non-sequential) half of the posterior trunk — hoist it
        out of the time scan and feed ``dynamic_projected``."""
        return self.representation_model.project_embed(embedded_obs)

    def _representation_projected(
        self,
        recurrent_state: jnp.ndarray,
        embed_proj: jnp.ndarray,
        key: Optional[jax.Array],
        gumbel: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        logits = uniform_mix(
            self.representation_model.from_projected(recurrent_state, embed_proj),
            self.discrete_size,
            self.unimix,
        )
        state = compute_stochastic_state(logits, self.discrete_size, key, gumbel=gumbel)
        return logits, jnp.reshape(state, state.shape[:-2] + (-1,))

    def dynamic(
        self,
        posterior: jnp.ndarray,
        recurrent_state: jnp.ndarray,
        action: jnp.ndarray,
        embedded_obs: jnp.ndarray,
        is_first: jnp.ndarray,
        key: jax.Array,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One posterior step with is_first resets (reference :352-404).

        All inputs are ``[B, ...]``; ``posterior`` flat ``[B, S*D]``. Returns
        ``(recurrent_state, posterior, posterior_logits, prior_logits)``.
        """
        return self.dynamic_projected(
            posterior, recurrent_state, action, self.project_embed(embedded_obs), is_first, key
        )

    def dynamic_projected(
        self,
        posterior: jnp.ndarray,
        recurrent_state: jnp.ndarray,
        action: jnp.ndarray,
        embed_proj: jnp.ndarray,
        is_first: jnp.ndarray,
        key: jax.Array,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """``dynamic`` with the embed projection precomputed (the train scan
        hoists ``project_embed`` over [T, B] outside the time loop)."""
        init_post = self._transition(
            (1.0 - is_first) * recurrent_state, None, sample_state=False
        )[1]
        recurrent_state, posterior, posterior_logits = self.dynamic_posterior(
            posterior, recurrent_state, action, embed_proj, is_first, init_post, key
        )
        prior_logits = self.prior_logits(recurrent_state)
        return recurrent_state, posterior, posterior_logits, prior_logits

    def dynamic_posterior(
        self,
        posterior: jnp.ndarray,
        recurrent_state: jnp.ndarray,
        action: jnp.ndarray,
        embed_proj: jnp.ndarray,
        is_first: jnp.ndarray,
        init_posterior: jnp.ndarray,
        key: Optional[jax.Array],
        gumbel: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Sequential core of ``dynamic``: only the posterior chain.

        The transition (prior) model never feeds back into the time loop —
        its logits depend only on the produced recurrent states — so train
        scans run this reduced step and batch :meth:`prior_logits` over the
        whole ``[T, B]`` output afterwards; likewise ``init_posterior`` (the
        prior mode at a zeroed recurrent state, constant) is computed once
        outside. Cuts the per-timestep weight streaming roughly in half.
        """
        action = (1.0 - is_first) * action
        recurrent_state = (1.0 - is_first) * recurrent_state
        posterior = (1.0 - is_first) * posterior + is_first * init_posterior
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        if gumbel is None:
            # same key split as dynamic() (whose k1 sampled the discarded
            # prior) so both paths draw the identical posterior sample stream
            key = jax.random.split(key)[1]
        posterior_logits, posterior = self._representation_projected(
            recurrent_state, embed_proj, key, gumbel=gumbel
        )
        return recurrent_state, posterior, posterior_logits

    def prior_logits(self, recurrent_states: jnp.ndarray) -> jnp.ndarray:
        """Unimixed transition logits — batchable over any leading shape."""
        return uniform_mix(
            self.transition_model(recurrent_states), self.discrete_size, self.unimix
        )

    def imagination(
        self,
        prior: jnp.ndarray,
        recurrent_state: jnp.ndarray,
        actions: jnp.ndarray,
        key: Optional[jax.Array],
        gumbel: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """One prior step in imagination (reference :441-457): flat prior in,
        flat sampled prior + new recurrent state out."""
        recurrent_state = self.recurrent_model(
            jnp.concatenate([prior, actions], -1), recurrent_state
        )
        _, imagined_prior = self._transition(recurrent_state, key, gumbel=gumbel)
        return imagined_prior, recurrent_state

    def __call__(self, posterior, recurrent_state, action, embedded_obs, is_first, key):
        return self.dynamic(posterior, recurrent_state, action, embedded_obs, is_first, key)


# ---------------------------------------------------------------------------
# world model
# ---------------------------------------------------------------------------


class MLPWithHead(nn.Module):
    """Dense trunk + single linear head (reward / continue / critic shape)."""

    output_dim: int
    mlp_layers: int
    dense_units: int
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            dtype=self.dtype,
        )(x)
        return nn.Dense(self.output_dim, dtype=self.dtype, name="head")(x).astype(jnp.float32)


class WorldModel(nn.Module):
    """Encoder + RSSM + observation/reward/continue heads (the canonical
    container from reference dreamer_v2/agent.py:714-739, reused by DV3).

    Methods are exposed for ``apply(..., method=...)`` so the train step can
    call exactly the piece it needs inside ``lax.scan``.
    """

    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_channels: Sequence[int]  # per-key channel counts (after frame-stack folding)
    mlp_dims: Sequence[int]
    image_size: Tuple[int, int]
    channels_multiplier: int
    stages: int
    encoder_mlp_layers: int
    decoder_mlp_layers: int
    dense_units: int
    recurrent_state_size: int
    stochastic_size: int
    discrete_size: int
    hidden_size: int
    reward_bins: int
    representation_hidden_size: Optional[int] = None
    reward_mlp_layers: Optional[int] = None
    reward_dense_units: Optional[int] = None
    continue_mlp_layers: Optional[int] = None
    continue_dense_units: Optional[int] = None
    layer_norm: bool = True
    unimix: float = 0.01
    cnn_act: Any = "silu"
    dense_act: Any = "silu"
    dtype: Optional[Any] = None

    def setup(self):
        self.encoder = MultiEncoderDV3(
            cnn_keys=self.cnn_keys,
            mlp_keys=self.mlp_keys,
            channels_multiplier=self.channels_multiplier,
            stages=self.stages,
            mlp_layers=self.encoder_mlp_layers,
            dense_units=self.dense_units,
            layer_norm=self.layer_norm,
            cnn_act=self.cnn_act,
            dense_act=self.dense_act,
            dtype=self.dtype,
        )
        # static encoder output width sizes the split posterior trunk kernel
        embed_size = MultiEncoderDV3.output_width(
            self.cnn_keys,
            self.mlp_keys,
            self.image_size,
            self.channels_multiplier,
            self.stages,
            self.dense_units,
        )
        self.rssm = RSSM(
            recurrent_state_size=self.recurrent_state_size,
            stochastic_size=self.stochastic_size,
            discrete_size=self.discrete_size,
            dense_units=self.dense_units,
            hidden_size=self.hidden_size,
            embed_size=embed_size,
            representation_hidden_size=self.representation_hidden_size,
            layer_norm=self.layer_norm,
            unimix=self.unimix,
            activation=self.dense_act,
            dtype=self.dtype,
        )
        if self.cnn_keys:
            self.cnn_decoder = CNNDecoder(
                output_channels=self.cnn_channels,
                channels_multiplier=self.channels_multiplier,
                stages=self.stages,
                image_size=self.image_size,
                layer_norm=self.layer_norm,
                activation=self.cnn_act,
                dtype=self.dtype,
            )
        if self.mlp_keys:
            self.mlp_decoder = MLPDecoder(
                keys=self.mlp_keys,
                output_dims=self.mlp_dims,
                mlp_layers=self.decoder_mlp_layers,
                dense_units=self.dense_units,
                layer_norm=self.layer_norm,
                activation=self.dense_act,
                dtype=self.dtype,
            )
        self.reward_model = MLPWithHead(
            output_dim=self.reward_bins,
            mlp_layers=self.reward_mlp_layers or self.decoder_mlp_layers,
            dense_units=self.reward_dense_units or self.dense_units,
            layer_norm=self.layer_norm,
            activation=self.dense_act,
            dtype=self.dtype,
        )
        self.continue_model = MLPWithHead(
            output_dim=1,
            mlp_layers=self.continue_mlp_layers or self.decoder_mlp_layers,
            dense_units=self.continue_dense_units or self.dense_units,
            layer_norm=self.layer_norm,
            activation=self.dense_act,
            dtype=self.dtype,
        )

    # -- methods for apply(..., method=...) --------------------------------

    def encode(self, obs: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        return self.encoder(obs)

    def dynamic(self, posterior, recurrent_state, action, embedded_obs, is_first, key):
        return self.rssm.dynamic(posterior, recurrent_state, action, embedded_obs, is_first, key)

    def project_embed(self, embedded_obs):
        return self.rssm.project_embed(embedded_obs)

    def dynamic_projected(self, posterior, recurrent_state, action, embed_proj, is_first, key):
        return self.rssm.dynamic_projected(
            posterior, recurrent_state, action, embed_proj, is_first, key
        )

    def dynamic_posterior(
        self,
        posterior,
        recurrent_state,
        action,
        embed_proj,
        is_first,
        init_posterior,
        key,
        gumbel=None,
    ):
        return self.rssm.dynamic_posterior(
            posterior, recurrent_state, action, embed_proj, is_first, init_posterior, key, gumbel
        )

    def prior_logits(self, recurrent_states):
        return self.rssm.prior_logits(recurrent_states)

    def imagination(self, prior, recurrent_state, actions, key, gumbel=None):
        return self.rssm.imagination(prior, recurrent_state, actions, key, gumbel=gumbel)

    def initial_posterior(self, recurrent_state: jnp.ndarray) -> jnp.ndarray:
        """Mode of the prior at a fresh recurrent state (player init,
        reference agent.py:516-537)."""
        return self.rssm._transition(recurrent_state, None, sample_state=False)[1]

    def recurrent_step(self, stochastic, actions, recurrent_state):
        return self.rssm.recurrent_model(
            jnp.concatenate([stochastic, actions], -1), recurrent_state
        )

    def representation(self, recurrent_state, embedded_obs, key):
        return self.rssm._representation(recurrent_state, embedded_obs, key)

    def decode(self, latent: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {}
        if self.cnn_keys:
            rec = self.cnn_decoder(latent)
            if len(self.cnn_keys) > 1:
                parts = jnp.split(rec, np.cumsum(np.asarray(self.cnn_channels))[:-1], axis=-3)
            else:
                parts = [rec]
            out.update({k: v for k, v in zip(self.cnn_keys, parts)})
        if self.mlp_keys:
            out.update(self.mlp_decoder(latent))
        return out

    def reward_logits(self, latent: jnp.ndarray) -> jnp.ndarray:
        return self.reward_model(latent)

    def continue_logits(self, latent: jnp.ndarray) -> jnp.ndarray:
        return self.continue_model(latent)

    def __call__(self, obs, posterior, recurrent_state, action, is_first, key):
        """Init-path: touches every submodule once."""
        embed = self.encoder(obs)
        recurrent_state, posterior, post_logits, prior_logits = self.rssm.dynamic(
            posterior, recurrent_state, action, embed, is_first, key
        )
        latent = jnp.concatenate([posterior, recurrent_state], -1)
        recon = self.decode(latent)
        return (
            recurrent_state,
            posterior,
            post_logits,
            prior_logits,
            recon,
            self.reward_model(latent),
            self.continue_model(latent),
        )


# ---------------------------------------------------------------------------
# actor / critic
# ---------------------------------------------------------------------------


class Actor(nn.Module):
    """DV3 actor (reference agent.py:588-767): dense trunk + one head per
    sub-action (discrete) or a single ``2*sum(dim)`` head (continuous).

    ``__call__`` returns the raw head outputs; distribution construction and
    sampling are pure functions below so they stay usable inside any jitted
    program.
    """

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str = "auto"
    dense_units: int = 1024
    mlp_layers: int = 5
    layer_norm: bool = True
    activation: Any = "silu"
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, state: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        x = MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_eps=1e-3,
            bias=not self.layer_norm,
            dtype=self.dtype,
        )(state)
        if self.is_continuous:
            return (
                nn.Dense(int(np.sum(self.actions_dim)) * 2, dtype=self.dtype, name="head_0")(x)
                .astype(jnp.float32),
            )
        return tuple(
            nn.Dense(dim, dtype=self.dtype, name=f"head_{i}")(x).astype(jnp.float32)
            for i, dim in enumerate(self.actions_dim)
        )


def resolve_actor_distribution(distribution: str, is_continuous: bool) -> str:
    dist = (distribution or "auto").lower()
    if dist not in ("auto", "normal", "tanh_normal", "discrete", "trunc_normal"):
        raise ValueError(
            "The distribution must be on of: `auto`, `discrete`, `normal`, "
            f"`tanh_normal` and `trunc_normal`. Found: {dist}"
        )
    if dist == "discrete" and is_continuous:
        raise ValueError("You have choose a discrete distribution but `is_continuous` is true")
    if dist == "auto":
        dist = "trunc_normal" if is_continuous else "discrete"
    return dist


def build_actor_dists(
    pre_dist: Sequence[jnp.ndarray],
    is_continuous: bool,
    distribution: str,
    init_std: float = 0.0,
    min_std: float = 0.1,
    unimix: float = 0.01,
) -> List[Any]:
    """Raw head outputs → per-sub-action distributions (reference :697-738)."""
    if is_continuous:
        mean, std = jnp.split(pre_dist[0], 2, axis=-1)
        if distribution == "tanh_normal":
            mean = 5.0 * jnp.tanh(mean / 5.0)
            std = jax.nn.softplus(std + init_std) + min_std
            return [Independent(TanhNormal(mean, std), 1)]
        if distribution == "normal":
            return [Independent(Normal(mean, std), 1)]
        if distribution == "trunc_normal":
            std = 2.0 * jax.nn.sigmoid((std + init_std) / 2.0) + min_std
            return [Independent(TruncatedNormal(jnp.tanh(mean), std, -1.0, 1.0), 1)]
        raise ValueError(f"Unknown continuous distribution '{distribution}'")
    dists = []
    for logits in pre_dist:
        probs = jax.nn.softmax(logits, axis=-1)
        if unimix > 0.0:
            probs = (1.0 - unimix) * probs + unimix / probs.shape[-1]
        dists.append(OneHotCategoricalStraightThrough(logits=jnp.log(probs)))
    return dists


def sample_actor_actions(
    dists: Sequence[Any], is_continuous: bool, key: jax.Array, is_training: bool = True
) -> List[jnp.ndarray]:
    """rsample when training; mode (discrete) / best-of-100 (continuous) for
    greedy evaluation (reference :714-738)."""
    keys = jax.random.split(key, len(dists))
    actions = []
    for d, k in zip(dists, keys):
        if is_training:
            actions.append(d.rsample(k))
        elif is_continuous:
            samples = d.sample(k, (100,))
            log_prob = d.log_prob(samples)
            best = jnp.argmax(log_prob, axis=0)
            actions.append(jnp.take_along_axis(samples, best[None, ..., None], axis=0)[0])
        else:
            actions.append(d.mode)
    return actions


def actor_entropy(dists: Sequence[Any], distribution: str) -> jnp.ndarray:
    """Summed per-head entropy; tanh_normal has no closed form → zeros
    (reference catches NotImplementedError at dreamer_v3.py:330-333)."""
    if distribution == "tanh_normal":
        base = dists[0].base.base  # Independent→TanhNormal→Normal
        return jnp.zeros(base.loc.shape[:-1], base.loc.dtype)
    return sum(d.entropy() for d in dists)


def add_exploration_noise(
    actions: Sequence[jnp.ndarray],
    expl_amount: jnp.ndarray,
    is_continuous: bool,
    key: jax.Array,
) -> List[jnp.ndarray]:
    """ε-exploration (reference :748-767): Gaussian noise clipped to [-1,1]
    (continuous) or uniform-resample with prob ε (discrete). ``expl_amount``
    is a dynamic scalar so decay never recompiles."""
    if is_continuous:
        cat = jnp.concatenate(actions, -1)
        noisy = jnp.clip(cat + expl_amount * jax.random.normal(key, cat.shape), -1.0, 1.0)
        return [jnp.where(expl_amount > 0.0, noisy, cat)]
    out = []
    keys = jax.random.split(key, 2 * len(actions))
    for i, act in enumerate(actions):
        rand = OneHotCategorical(logits=jnp.zeros_like(act)).sample(keys[2 * i])
        take = jax.random.uniform(keys[2 * i + 1], act.shape[:-1] + (1,)) < expl_amount
        out.append(jnp.where(take, rand, act))
    return out


# ---------------------------------------------------------------------------
# Hafner initialization
# ---------------------------------------------------------------------------

_TRUNC_STD_FACTOR = 0.87962566103423978


def _fans(shape: Tuple[int, ...]) -> Tuple[float, float]:
    if len(shape) == 2:  # dense [in, out]
        return float(shape[0]), float(shape[1])
    if len(shape) == 4:  # conv [kh, kw, in, out] (flax layout)
        space = shape[0] * shape[1]
        return float(space * shape[2]), float(space * shape[3])
    return float(np.prod(shape[:-1])), float(shape[-1])


def hafner_initialization(
    params: Dict[str, Any], key: jax.Array, uniform_heads: Sequence[Tuple[str, float]] = ()
) -> Dict[str, Any]:
    """Re-initialize every kernel with the Hafner scheme (reference
    dreamer_v3/utils.py init_weights/uniform_init_weights + the head overrides
    in build_models :1109-1119).

    - default: truncated normal, std = sqrt(1/mean(fan_in, fan_out)) / 0.8796,
      truncated at ±2σ;
    - ``uniform_heads``: (path-regex, scale) pairs; matching kernels get
      U(−limit, limit) with limit = sqrt(3·scale/mean(fan)); scale 0 → zeros.

    Biases / norm params keep flax defaults (zeros / ones), which is what the
    reference sets too.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n = len(flat)
    keys = jax.random.split(key, max(n, 1))
    compiled = [(re.compile(pat), scale) for pat, scale in uniform_heads]

    def path_str(path) -> str:
        return "/".join(getattr(p, "key", str(p)) for p in path)

    new_leaves = {}
    for i, (path, leaf) in enumerate(flat):
        p = path_str(path)
        if not p.endswith("kernel") or leaf.ndim < 2:
            new_leaves[p] = leaf
            continue
        fan_in, fan_out = _fans(leaf.shape)
        denom = (fan_in + fan_out) / 2.0
        matched = None
        for pat, scale in compiled:
            if pat.search(p):
                matched = scale
                break
        if matched is not None:
            if matched == 0.0:
                new_leaves[p] = jnp.zeros_like(leaf)
            else:
                limit = math.sqrt(3.0 * matched / denom)
                new_leaves[p] = jax.random.uniform(
                    keys[i], leaf.shape, leaf.dtype, -limit, limit
                )
        else:
            std = math.sqrt(1.0 / denom) / _TRUNC_STD_FACTOR
            new_leaves[p] = std * jax.random.truncated_normal(
                keys[i], -2.0, 2.0, leaf.shape, leaf.dtype
            )

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: new_leaves[path_str(path)], params
    )


# DV3 head overrides (reference build_models :1109-1119)
WM_UNIFORM_HEADS = (
    (r"reward_model/head/", 0.0),
    (r"rssm/transition_model/head/", 1.0),
    (r"rssm/representation_model/head/", 1.0),
    (r"continue_model/head/", 1.0),
    (r"mlp_decoder/head_", 1.0),
    (r"cnn_decoder/head/", 1.0),
)
ACTOR_UNIFORM_HEADS = ((r"head_\d+/", 1.0),)
CRITIC_UNIFORM_HEADS = ((r"head/", 0.0),)


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def build_agent(
    cfg,
    actions_dim: Sequence[int],
    is_continuous: bool,
    observation_space,
    key: jax.Array,
    sequence_models: Optional[Sequence[str]] = None,
) -> Tuple[WorldModel, Actor, MLPWithHead, Dict[str, Any]]:
    """Construct module defs + initialized params (reference build_models,
    agent.py:900-1144). Returns ``(world_model, actor, critic, params)`` with
    ``params = {world_model, actor, critic, target_critic}``.

    ``sequence_models`` names the world-model cores the calling entrypoint
    can train (``algo.world_model.sequence_model``); left out, it follows
    from ``algo.name``: ``dreamer_v3`` trains every core of
    ``seq_agent.SEQUENCE_MODELS``, every other entrypoint that builds its
    agent here (P2E-DV3) the GRU alone. Another core is refused here, with
    the reason, rather than inside a trace."""
    wm_cfg = cfg.algo.world_model
    sequence_model = str(wm_cfg.get("sequence_model", "gru") or "gru")
    if sequence_models is None:
        from sheeprl_tpu.algos.dreamer_v3.seq_agent import SEQUENCE_MODELS

        sequence_models = SEQUENCE_MODELS if cfg.algo.name == "dreamer_v3" else ("gru",)
    if sequence_model not in sequence_models:
        raise ValueError(
            f"algo.world_model.sequence_model={sequence_model!r} is not supported by this entrypoint "
            f"({cfg.algo.name}); it trains {list(sequence_models)}. The sequence cores are "
            "dreamer_v3's (howto/sequence_core.md)."
        )
    if sequence_model != "gru":
        from sheeprl_tpu.algos.dreamer_v3.seq_agent import build_seq_agent

        return build_seq_agent(cfg, actions_dim, is_continuous, observation_space, key)
    cnn_keys = list(cfg.cnn_keys.encoder)
    mlp_keys = list(cfg.mlp_keys.encoder)
    screen = int(cfg.env.screen_size)
    stages = int(np.log2(screen)) - 2
    # fabric.precision=bf16-mixed: bf16 compute with f32 params and f32
    # losses/logits (heads cast back); 32-true keeps everything f32
    from sheeprl_tpu.fabric import compute_dtype_from_precision

    compute_dtype = compute_dtype_from_precision(cfg.fabric.get("precision", "32-true"))
    cnn_channels = [
        int(np.prod(observation_space[k].shape[:-2])) for k in cnn_keys
    ]
    mlp_dims = [int(np.prod(observation_space[k].shape)) for k in mlp_keys]

    world_model = WorldModel(
        cnn_keys=cnn_keys,
        mlp_keys=mlp_keys,
        cnn_channels=cnn_channels,
        mlp_dims=mlp_dims,
        image_size=(screen, screen),
        channels_multiplier=int(wm_cfg.encoder.cnn_channels_multiplier),
        stages=stages,
        encoder_mlp_layers=int(wm_cfg.encoder.mlp_layers),
        decoder_mlp_layers=int(wm_cfg.observation_model.mlp_layers),
        dense_units=int(wm_cfg.encoder.dense_units),
        recurrent_state_size=int(wm_cfg.recurrent_model.recurrent_state_size),
        stochastic_size=int(wm_cfg.stochastic_size),
        discrete_size=int(wm_cfg.discrete_size),
        hidden_size=int(wm_cfg.transition_model.hidden_size),
        representation_hidden_size=int(wm_cfg.representation_model.hidden_size),
        reward_bins=int(wm_cfg.reward_model.bins),
        reward_mlp_layers=int(wm_cfg.reward_model.mlp_layers),
        reward_dense_units=int(wm_cfg.reward_model.dense_units),
        continue_mlp_layers=int(wm_cfg.discount_model.mlp_layers),
        continue_dense_units=int(wm_cfg.discount_model.dense_units),
        layer_norm=bool(cfg.algo.layer_norm),
        unimix=float(cfg.algo.unimix),
        cnn_act=cfg.algo.cnn_act,
        dense_act=cfg.algo.dense_act,
        dtype=compute_dtype,
    )
    latent_size = (
        int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
        + int(wm_cfg.recurrent_model.recurrent_state_size)
    )
    actor = Actor(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=resolve_actor_distribution(
            cfg.distribution.get("type", "auto"), is_continuous
        ),
        dense_units=int(cfg.algo.actor.dense_units),
        mlp_layers=int(cfg.algo.actor.mlp_layers),
        layer_norm=bool(cfg.algo.actor.layer_norm),
        activation=cfg.algo.actor.dense_act,
        dtype=compute_dtype,
    )
    critic = MLPWithHead(
        output_dim=int(cfg.algo.critic.bins),
        mlp_layers=int(cfg.algo.critic.mlp_layers),
        dense_units=int(cfg.algo.critic.dense_units),
        layer_norm=bool(cfg.algo.critic.layer_norm),
        activation=cfg.algo.critic.dense_act,
        dtype=compute_dtype,
    )

    k_wm, k_actor, k_critic, k_hw, k_ha, k_hc, k_s = jax.random.split(key, 7)
    dummy_obs = {}
    for k, ch in zip(cnn_keys, cnn_channels):
        dummy_obs[k] = jnp.zeros((1, ch, screen, screen), jnp.float32)
    for k, dim in zip(mlp_keys, mlp_dims):
        dummy_obs[k] = jnp.zeros((1, dim), jnp.float32)
    stoch = int(wm_cfg.stochastic_size) * int(wm_cfg.discrete_size)
    rec = int(wm_cfg.recurrent_model.recurrent_state_size)
    act_dim = int(np.sum(actions_dim))

    wm_params = world_model.init(
        k_wm,
        dummy_obs,
        jnp.zeros((1, stoch)),
        jnp.zeros((1, rec)),
        jnp.zeros((1, act_dim)),
        jnp.zeros((1, 1)),
        k_s,
    )["params"]
    actor_params = actor.init(k_actor, jnp.zeros((1, latent_size)))["params"]
    critic_params = critic.init(k_critic, jnp.zeros((1, latent_size)))["params"]

    if bool(cfg.algo.hafner_initialization):
        wm_params = hafner_initialization(wm_params, k_hw, WM_UNIFORM_HEADS)
        actor_params = hafner_initialization(actor_params, k_ha, ACTOR_UNIFORM_HEADS)
        critic_params = hafner_initialization(critic_params, k_hc, CRITIC_UNIFORM_HEADS)

    params = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
        "target_critic": jax.tree_util.tree_map(jnp.copy, critic_params),
    }
    return world_model, actor, critic, params


# ---------------------------------------------------------------------------
# functional player (reference PlayerDV3, agent.py:460-585)
# ---------------------------------------------------------------------------


def acting_params(wm_params):
    """The part of a world-model parameter tree that acting reads.

    The player functions of :func:`build_player_fns` call four
    :class:`WorldModel` methods — ``encode`` (``encoder``), ``recurrent_step``,
    ``representation`` and ``initial_posterior`` (all three ``rssm``) — and
    flax looks a parameter up only when its module runs, so these two
    subtrees are a complete ``wm_params`` for every one of them. The decoders
    and the reward and continue heads are training's alone: a caller that
    copies parameters to where acting runs (``utils/host.py``) hands over
    this selection, not the whole tree. A sequence core (``seq_agent.py``)
    acts through its encoder, its posterior head and the whole core.
    """
    if "core" in wm_params:
        return {k: wm_params[k] for k in ("encoder", "posterior", "core")}
    return {"encoder": wm_params["encoder"], "rssm": wm_params["rssm"]}


def build_player_fns(
    world_model: WorldModel,
    actor: Actor,
    cfg,
    actions_dim: Sequence[int],
    is_continuous: bool,
):
    """Pure jitted player functions over an explicit state pytree
    ``{"actions", "recurrent", "stochastic"}`` (each ``[n_envs, ...]``).

    Replaces the reference's mutable PlayerDV3 (agent.py:516-585); per-env
    resets are ``jnp.where`` masks so vectorized-env episode ends never leave
    jit.
    """
    distribution = resolve_actor_distribution(
        cfg.distribution.get("type", "auto"), is_continuous
    )
    init_std = float(cfg.algo.actor.init_std)
    min_std = float(cfg.algo.actor.min_std)
    unimix = float(cfg.algo.unimix)
    rec_size = int(cfg.algo.world_model.recurrent_model.recurrent_state_size)
    act_dim = int(np.sum(actions_dim))
    # MineDojo envs carry per-step validity masks: route sampling and
    # exploration noise through the mask-aware actor (reference dispatches a
    # MinedojoActor subclass via cfg.algo.actor.cls; here the same head
    # layout takes a `masks` kwarg — minedojo_actor.py)
    minedojo = "minedojo" in str(cfg.env.wrapper.get("_target_", "") or "").lower()

    def init_states(wm_params, n_envs: int):
        recurrent = jnp.tanh(jnp.zeros((n_envs, rec_size)))
        stochastic = world_model.apply(
            {"params": wm_params}, recurrent, method=WorldModel.initial_posterior
        )
        return {
            "actions": jnp.zeros((n_envs, act_dim)),
            "recurrent": recurrent,
            "stochastic": stochastic,
        }

    def reset_states(wm_params, state, reset_mask):
        """``reset_mask``: [n_envs, 1] float — 1 resets that env's state."""
        fresh = init_states(wm_params, state["actions"].shape[0])
        return jax.tree_util.tree_map(
            lambda f, s: reset_mask * f + (1.0 - reset_mask) * s, fresh, state
        )

    def _step(wm_params, actor_params, state, obs, key, is_training: bool, masks=None):
        embed = world_model.apply({"params": wm_params}, obs, method=WorldModel.encode)
        recurrent = world_model.apply(
            {"params": wm_params},
            state["stochastic"],
            state["actions"],
            state["recurrent"],
            method=WorldModel.recurrent_step,
        )
        k_repr, k_act = jax.random.split(key)
        _, stochastic = world_model.apply(
            {"params": wm_params}, recurrent, embed, k_repr, method=WorldModel.representation
        )
        latent = jnp.concatenate([stochastic, recurrent], -1)
        pre_dist = actor.apply({"params": actor_params}, latent)
        if minedojo and masks is not None:
            from sheeprl_tpu.algos.dreamer_v3.minedojo_actor import sample_minedojo_actions

            actions, _ = sample_minedojo_actions(
                pre_dist, masks, k_act, unimix, is_training
            )
        else:
            dists = build_actor_dists(
                pre_dist, is_continuous, distribution, init_std, min_std, unimix
            )
            actions = sample_actor_actions(dists, is_continuous, k_act, is_training)
        new_state = {
            "actions": jnp.concatenate(actions, -1),
            "recurrent": recurrent,
            "stochastic": stochastic,
        }
        return actions, new_state

    @jax.jit
    def greedy_action(wm_params, actor_params, state, obs, key, masks=None):
        return _step(wm_params, actor_params, state, obs, key, is_training=False, masks=masks)

    @jax.jit
    def exploration_action(wm_params, actor_params, state, obs, key, expl_amount, masks=None):
        k_step, k_expl = jax.random.split(key)
        actions, new_state = _step(
            wm_params, actor_params, state, obs, k_step, is_training=True, masks=masks
        )
        if minedojo and masks is not None:
            from sheeprl_tpu.algos.dreamer_v3.minedojo_actor import (
                add_minedojo_exploration_noise,
            )

            expl = add_minedojo_exploration_noise(actions, expl_amount, masks, k_expl)
        else:
            expl = add_exploration_noise(actions, expl_amount, is_continuous, k_expl)
        new_state = dict(new_state, actions=jnp.concatenate(expl, -1))
        return expl, new_state

    # raw-obs variants: normalization happens INSIDE the jit, so acting is a
    # single dispatch taking native-dtype (uint8 pixel) host arrays: the
    # eager normalize of the plain variants is one extra dispatch per obs
    # key per env step, and f32 pixels are 4x the uint8 upload.
    cnn_keys = tuple(cfg.cnn_keys.encoder)

    def _normalize(raw_obs):
        from sheeprl_tpu.algos.dreamer_v3.utils import normalize_obs_jnp

        return normalize_obs_jnp(raw_obs, cnn_keys)

    @jax.jit
    def greedy_action_raw(wm_params, actor_params, state, raw_obs, key, masks=None):
        return _step(
            wm_params, actor_params, state, _normalize(raw_obs), key,
            is_training=False, masks=masks,
        )

    @jax.jit
    def exploration_action_raw(
        wm_params, actor_params, state, raw_obs, key, expl_amount, masks=None
    ):
        return exploration_action(
            wm_params, actor_params, state, _normalize(raw_obs), key, expl_amount,
            masks=masks,
        )

    return {
        # one dispatch: acting on the device makes it after every burst (PERF.md, PR 35)
        "init_states": jax.jit(init_states, static_argnums=1),
        "reset_states": jax.jit(reset_states),
        "greedy_action": greedy_action,
        "exploration_action": exploration_action,
        "greedy_action_raw": greedy_action_raw,
        "exploration_action_raw": exploration_action_raw,
    }

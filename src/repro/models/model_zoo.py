"""Model bundle: one uniform interface over all assigned architectures.

``build_model(cfg)`` returns a :class:`Model` whose members are pure
functions — ``init``, ``loss_fn``, ``forward``, ``prefill``,
``decode_step``, ``init_cache`` — plus ``input_specs`` /``cache_specs``
(ShapeDtypeStruct stand-ins for the dry-run; no allocation).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, transformer
from repro.models.layers import dtype_of

Identity = lambda x, where="boundary": x  # noqa: E731


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    def init(self, rng: jax.Array):
        """-> (params, logical_axes)"""
        if self.cfg.family == "audio":
            return encdec.init_params(self.cfg, rng)
        return transformer.init_params(self.cfg, rng)

    def axes_tree(self) -> Any:
        """Logical-axes tree (cheap: tuples are static, but building them
        requires running init abstractly)."""
        out = {}

        def capture(rng):
            params, axes = (encdec.init_params(self.cfg, rng)
                            if self.cfg.family == "audio"
                            else transformer.init_params(self.cfg, rng))
            out["axes"] = axes
            return params

        jax.eval_shape(capture, jax.random.key(0))
        return out["axes"]

    def abstract_params(self) -> Any:
        """ShapeDtypeStruct tree of params (dry-run; no allocation)."""
        return jax.eval_shape(lambda r: self.init(r)[0], jax.random.key(0))

    # -- functional entry points -------------------------------------
    def loss_fn(self, params, batch, *, backend="xla",
                shard_fn: Callable = Identity, remat="full",
                schedules=None):
        if self.cfg.family == "audio":
            return encdec.loss_fn(params, self.cfg, batch,
                                  backend=backend, shard_fn=shard_fn,
                                  remat=remat)
        return transformer.loss_fn(params, self.cfg, batch,
                                   backend=backend, shard_fn=shard_fn,
                                   remat=remat, schedules=schedules)

    def forward(self, params, batch, *, backend="xla",
                shard_fn: Callable = Identity, schedules=None,
                seq_starts=None):
        if self.cfg.family == "audio":
            if seq_starts is not None:
                raise ValueError(
                    "seq_starts is not supported for family 'audio'")
            return encdec.forward(params, self.cfg, batch,
                                  backend=backend, shard_fn=shard_fn)
        return transformer.forward(params, self.cfg, batch,
                                   backend=backend, shard_fn=shard_fn,
                                   schedules=schedules,
                                   seq_starts=seq_starts)

    def prefill(self, params, batch, *, backend="xla",
                shard_fn: Callable = Identity, schedules=None,
                seq_starts=None):
        if self.cfg.family == "audio":
            if seq_starts is not None:
                raise ValueError(
                    "seq_starts is not supported for family 'audio'")
            return encdec.prefill(params, self.cfg, batch,
                                  backend=backend, shard_fn=shard_fn)
        return transformer.prefill(params, self.cfg, batch,
                                   backend=backend, shard_fn=shard_fn,
                                   schedules=schedules,
                                   seq_starts=seq_starts)

    def decode_step(self, params, cache, tokens, pos, *,
                    shard_fn: Callable = Identity, backend="xla",
                    schedules=None, seq_starts=None, block_tables=None):
        if self.cfg.family == "audio":
            if seq_starts is not None or block_tables is not None:
                raise ValueError(
                    "seq_starts/block_tables are not supported for "
                    "family 'audio'")
            return encdec.decode_step(params, self.cfg, cache, tokens,
                                      pos, shard_fn=shard_fn,
                                      backend=backend,
                                      schedules=schedules)
        return transformer.decode_step(params, self.cfg, cache, tokens,
                                       pos, shard_fn=shard_fn,
                                       backend=backend,
                                       schedules=schedules,
                                       seq_starts=seq_starts,
                                       block_tables=block_tables)

    def init_cache(self, bsz: int, max_len: int, dtype=None):
        if self.cfg.family == "audio":
            return encdec.init_cache(self.cfg, bsz, max_len, dtype)
        return transformer.init_cache(self.cfg, bsz, max_len, dtype)

    def init_paged_cache(self, n_blocks: int, block_size: int,
                         dtype=None):
        """Block-paged KV pools (attention families only); see
        :func:`repro.models.transformer.init_paged_cache`."""
        return transformer.init_paged_cache(self.cfg, n_blocks,
                                            block_size, dtype)

    # -- dry-run stand-ins -------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStructs for the batch of a (train|prefill) step, or
        for (tokens, pos) of a decode step."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        dt = dtype_of(cfg.dtype)
        i32 = jnp.int32
        if shape.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
                    "pos": jax.ShapeDtypeStruct((), i32)}
        specs: Dict[str, Any] = {}
        if cfg.family == "vlm":
            s_text = s - cfg.num_image_tokens
            specs["tokens"] = jax.ShapeDtypeStruct((b, s_text), i32)
            specs["image_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.num_image_tokens, cfg.d_model), dt)
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((b, s), i32)
            return specs
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), dt)
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        return specs

    def cache_specs(self, shape: ShapeConfig) -> Any:
        """ShapeDtypeStruct tree of the decode cache for a shape cell."""
        return jax.eval_shape(
            functools.partial(self.init_cache, shape.global_batch,
                              shape.seq_len))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg)


# ---------------------------------------------------------------------------
# Shape-bucket padding helpers (serving sessions)
# ---------------------------------------------------------------------------
#
# A serving session batches heterogeneous prompts by padding them up to a
# small set of bucket lengths, so the compiled prefill/decode executables
# are shared across requests instead of re-lowered per prompt length.

def bucket_length(n: int, lengths: Optional[Tuple[int, ...]] = None,
                  align: int = 8) -> int:
    """Smallest padded length that fits ``n`` tokens.

    With an explicit ``lengths`` grid, the smallest grid entry >= n
    (raises if none fits); otherwise the smallest power of two >= n,
    floored at ``align``.
    """
    if n <= 0:
        raise ValueError(f"cannot bucket a length of {n}")
    if lengths:
        fitting = [int(b) for b in lengths if b >= n]
        if not fitting:
            raise ValueError(
                f"no bucket in {sorted(lengths)} fits length {n}")
        return min(fitting)
    m = align
    while m < n:
        m *= 2
    return m


def left_pad_prompts(prompts, target_len: int, pad_id: int = 0):
    """Stack variable-length 1-D token prompts into one [B, target_len]
    int32 array, left-padded with ``pad_id``.

    Left padding keeps every prompt's *last* token at the same position,
    so a batch of mixed-length prompts shares one decode position
    counter (the model's ``decode_step`` takes a scalar ``pos``).  Pass
    the matching :func:`prompt_starts` vector as ``seq_starts`` to
    ``prefill``/``decode_step`` so pad tokens are masked out of
    attention (and out of the SSM recurrence): a padded row then
    produces the same logits as its unpadded equivalent, up to float
    reduction order.
    """
    import numpy as np
    out = np.full((len(prompts), target_len), int(pad_id), dtype=np.int32)
    for i, p in enumerate(prompts):
        p = np.asarray(p, dtype=np.int32).reshape(-1)
        if len(p) > target_len:
            raise ValueError(
                f"prompt of length {len(p)} exceeds bucket {target_len}")
        if len(p):
            out[i, target_len - len(p):] = p
    return out


def prompt_starts(prompts, target_len: int):
    """[B] int32 of each left-padded row's first real token index
    (``target_len - len(prompt)``) — the ``seq_starts`` companion to
    :func:`left_pad_prompts`."""
    import numpy as np
    starts = np.empty((len(prompts),), dtype=np.int32)
    for i, p in enumerate(prompts):
        n = int(np.asarray(p).reshape(-1).shape[0])
        if n > target_len:
            raise ValueError(
                f"prompt of length {n} exceeds bucket {target_len}")
        starts[i] = target_len - n
    return starts

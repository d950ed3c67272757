"""Stateless per-task random number generation (ThundeRiNG analogue, §VII).

The paper pairs each sampling module with ThundeRiNG, an on-chip RNG that
produces decorrelated streams with zero HBM traffic (unlike FastRW, which
pre-generates randoms on the host and burns HBM bandwidth loading them).

On TPU the exact analogue is JAX's counter-based Threefry: the random draw
for a task is a *pure function of the task tuple* ``(seed, query_id, hop)``
— which makes the draw itself stateless, so a task can be executed on any
device, at any time, in any order, and still produce the identical sample.
This is the RNG-side half of the paper's Markov-based stateless
decomposition (§V-A): reordering and re-routing tasks provably cannot
change the sampled walk distribution because the randomness travels with
the task identity, not with the execution site.

Open-system slot reuse extends the task identity with an *epoch*: when the
streaming engine reclaims a finished query's buffer slot (ring-buffer
economy), the next occupant of slot ``qid`` carries ``epoch + 1`` and its
draws derive from ``(seed, epoch, qid, hop)``.  Epoch 0 folds nothing
extra, so it is bit-for-bit the classic ``(seed, query_id, hop)``
derivation — a closed-batch run *is* epoch 0 of a stream, and epoch ``e``
of any stream equals a closed-batch run under :func:`stream_key`'s
epoch-salted base key.

Shared Threefry core
--------------------
:func:`threefry2x32` is an explicit, shape-agnostic implementation of the
Threefry-2x32 block cipher that is bit-equal to ``jax.random``'s.
:func:`key_bits` lays draws out as ``jax.random.bits`` does under
``jax.threefry_partitionable(False)`` — two 32-bit draws per cipher block,
counters split in halves — which is *not* JAX's default layout since 0.5
(one block per draw, output words XORed).  ``tests/test_fused_step.py``
pins the equality against that non-partitionable layout; the walks
themselves never call ``jax.random`` for draws, so the JAX default does
not change them.  It exists so the *same*
derivation runs in two places:

  * the vectorized jnp superstep (``task_uniforms`` below — now direct
    uint32 vector math instead of a ``vmap`` of ``jax.random.fold_in``),
  * inside the fused Pallas superstep kernel
    (`repro.kernels.fused_superstep`), where per-lane draws are computed
    on SMEM scalars with zero HBM traffic — the literal ThundeRiNG
    analogue.

Both paths therefore sample identical walks for identical
``(seed, epoch, query_id, hop, salt)`` tuples, which is what makes
``step_impl="fused"`` bit-identical to ``step_impl="jnp"``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# Salt registry: the single source of truth for every salt channel.
#
# A task's draw stream is keyed by (seed, epoch, query_id, hop, salt) — two
# streams with distinct salts are disjoint (the salt folds into the Threefry
# key), so the whole RNG-collision argument reduces to: no two independent
# uses share a salt.  Every SALT_* constant in the codebase is registered
# here, uniqueness is asserted at import, and the static analyzer
# (`repro.analysis`) reads this registry as ground truth when it proves the
# per-sampler draw streams pairwise disjoint.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SaltChannel:
    """One registered salt channel.

    A scalar channel owns exactly the value ``value``.  A *family*
    (``family=True``) owns the open-ended range ``[value, ∞)`` — the
    reservoir chunk draws use ``SALT_CHUNK0 + c`` for chunk ``c`` with a
    data-dependent (degree-bounded, statically unbounded) chunk count, so
    the family must sit above every scalar channel.
    """

    name: str
    value: int
    family: bool = False

    def covers(self, salt: int) -> bool:
        """Does this channel own the concrete salt value ``salt``?"""
        return salt >= self.value if self.family else salt == self.value


class SaltRegistry:
    """Name → :class:`SaltChannel` registry with import-time disjointness.

    ``register`` raises immediately when a new channel overlaps an
    existing one (duplicate scalar value, scalar inside a family's range,
    or a second open-ended family — two unbounded families always
    overlap), so a bad salt constant can never make it past import.
    """

    def __init__(self):
        self._channels: Dict[str, SaltChannel] = {}

    def register(self, name: str, value: int, family: bool = False) -> int:
        ch = SaltChannel(name, int(value), family)
        if name in self._channels:
            raise ValueError(f"salt channel {name!r} registered twice")
        for other in self._channels.values():
            span = self._overlap(ch, other)
            if span is not None:
                lo, hi = span
                rng_s = f"[{lo}, ∞)" if hi is None else f"[{lo}, {hi})"
                raise ValueError(
                    f"salt channel {name}={value!r} overlaps "
                    f"{other.name}={other.value!r} on {rng_s} — every "
                    f"salt channel must own a disjoint value range")
        self._channels[name] = ch
        return ch.value

    @staticmethod
    def _overlap(a: SaltChannel,
                 b: SaltChannel) -> Optional[Tuple[int, Optional[int]]]:
        """Overlap interval of two channels' owned ranges, or None."""
        if a.family and b.family:
            return (max(a.value, b.value), None)
        if a.family or b.family:
            fam, sc = (a, b) if a.family else (b, a)
            return (sc.value, sc.value + 1) if sc.value >= fam.value else None
        return (a.value, a.value + 1) if a.value == b.value else None

    def channels(self) -> Tuple[SaltChannel, ...]:
        return tuple(self._channels.values())

    def lookup(self, salt: int) -> Optional[SaltChannel]:
        """The channel owning concrete salt value ``salt``, if any."""
        for ch in self._channels.values():
            if ch.covers(int(salt)):
                return ch
        return None

    def names(self) -> Tuple[str, ...]:
        return tuple(self._channels)

    def __getitem__(self, name: str) -> SaltChannel:
        return self._channels[name]

    def __contains__(self, name: str) -> bool:
        return name in self._channels


#: The registry instance — all salt channels in the system, in one place.
SALTS = SaltRegistry()

# Salt channels for decorrelated draws within one hop.  `samplers.py` and
# the kernels import these (never redefine them); the `repro.analysis` RNG
# pass cross-checks every `task_*` call site against this registry.
SALT_COLUMN = SALTS.register("SALT_COLUMN", 0)   # which neighbor column
SALT_ACCEPT = SALTS.register("SALT_ACCEPT", 1)   # alias/rejection accept
SALT_STOP = SALTS.register("SALT_STOP", 2)       # PPR termination draw
# Corpus-consumer channels (`core/corpus_ring.py`): the SGNS batch sampler
# draws (ring row, center position, window offset) and the negative ids
# from the same (seed, qid, hop) fold space a walk task of round 0 uses
# (batch element i at grad step t folds qid=i, hop=t), so its channels
# must be registry-disjoint from every sampler/engine channel — the
# `repro.analysis` rng pass proves it.
SALT_CORPUS = SALTS.register("SALT_CORPUS", 3)       # window draw (row/c/off)
SALT_NEGATIVE = SALTS.register("SALT_NEGATIVE", 4)   # SGNS negative ids
# Reservoir chunk draws: chunk c draws at SALT_CHUNK0 + c, an open-ended
# family (chunk counts are degree-dependent), so it must sit above every
# scalar channel — the registry enforces that at import.
SALT_CHUNK0 = SALTS.register("SALT_CHUNK0", 8, family=True)


# Threefry-2x32 key-schedule parity constant (Salmon et al., SC'11).
_THREEFRY_PARITY = np.uint32(0x1BD11BDA)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k0, k1, x0, x1):
    """One Threefry-2x32 block: encrypt counter ``(x0, x1)`` under key
    ``(k0, k1)``; returns the two output words.

    Shape-agnostic uint32 math (scalars inside a Pallas kernel, (W,) or
    (W, P) arrays in the jnp path) — bit-equal to the ``threefry2x32``
    primitive ``jax.random`` lowers to, pinned by tests.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(x0, jnp.uint32)
    x1 = jnp.asarray(x1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ _THREEFRY_PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << r) | (x1 >> (32 - r))
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def fold_in_pair(k0, k1, data):
    """``jax.random.fold_in`` on an explicit (k0, k1) key pair: the data
    word is encrypted as the counter ``(0, data)`` (a 32-bit datum's high
    word is zero), yielding the folded key pair."""
    data = jnp.asarray(data, jnp.uint32)
    return threefry2x32(k0, k1, jnp.zeros_like(data), data)


def task_key_pair(k0, k1, query_id, hop, salt, epoch=None):
    """Per-task key pair from (seed[, epoch], query_id, hop, salt) — the
    fold chain of :func:`task_fold` on explicit uint32 words (usable on
    SMEM scalars inside a kernel).  ``epoch`` 0 / None reproduces the
    legacy 3-tuple derivation bit-exactly."""
    qid = jnp.asarray(query_id, jnp.uint32)
    if epoch is not None:
        e = jnp.asarray(epoch, jnp.int32)
        s0, s1 = fold_in_pair(k0, k1, e.astype(jnp.uint32))
        use_salted = e > 0
        k0 = jnp.where(use_salted, s0, jnp.broadcast_to(
            jnp.asarray(k0, jnp.uint32), s0.shape))
        k1 = jnp.where(use_salted, s1, jnp.broadcast_to(
            jnp.asarray(k1, jnp.uint32), s1.shape))
    k0, k1 = fold_in_pair(k0, k1, qid)
    k0, k1 = fold_in_pair(k0, k1, jnp.asarray(hop, jnp.uint32))
    return fold_in_pair(k0, k1, jnp.asarray(salt, jnp.uint32))


def bits_to_uniform(bits):
    """uint32 random bits -> U[0, 1) float32, bit-equal to
    ``jax.random.uniform``'s mantissa trick (top 23 bits as the mantissa
    of a float in [1, 2), minus 1): that value is exactly ``m * 2**-23``
    for ``m = bits >> 9``, and both the int -> float conversion of a
    23-bit ``m`` and the power-of-two scale are exact.  Written without a
    bitcast so it also lowers on SMEM scalars inside a Pallas TPU kernel
    (Mosaic's ``tpu.bitcast`` takes vectors only)."""
    m = (jnp.asarray(bits, jnp.uint32) >> np.uint32(9)).astype(jnp.int32)
    return m.astype(jnp.float32) * np.float32(2.0 ** -23)


def _counter_pairs(num: int):
    """Counter words for ``num`` 32-bit draws, split as the
    non-partitionable ``threefry_2x32`` layout does (odd sizes pad one
    zero)."""
    pairs = (num + 1) // 2
    x0 = np.arange(pairs, dtype=np.uint32)
    x1 = np.where(np.arange(pairs) + pairs < num,
                  np.arange(pairs) + pairs, 0).astype(np.uint32)
    return x0, x1


def key_bits(k0, k1, num: int):
    """``num`` uint32 words from a key pair — bit-equal to
    ``jax.random.bits(key, (num,), jnp.uint32)`` under
    ``jax.threefry_partitionable(False)``.  ``k0``/``k1`` may carry
    leading batch dims; the draw axis is appended last."""
    x0, x1 = _counter_pairs(num)
    k0 = jnp.asarray(k0, jnp.uint32)[..., None]
    k1 = jnp.asarray(k1, jnp.uint32)[..., None]
    y0, y1 = threefry2x32(k0, k1, x0[None, :], x1[None, :])
    return jnp.concatenate([y0, y1], axis=-1)[..., :num]


def task_fold(base_key: jax.Array, query_id: jnp.ndarray, hop: jnp.ndarray,
              salt=0, epoch=None) -> jax.Array:
    """Derive one PRNG key per task from (seed[, epoch], query_id, hop, salt).

    ``salt`` decorrelates independent uses within the same hop (sampler
    column draw vs. accept test vs. PPR stop draw vs. reservoir chunk).
    ``epoch`` (per-task, optional) decorrelates successive occupants of a
    reused query slot; epoch 0 (or None) reproduces the legacy 3-tuple
    derivation exactly, so closed-batch walks are unchanged.

    Returns a (W, 2) uint32 key array, bit-equal to the historical
    ``vmap(fold_in∘fold_in∘fold_in)`` derivation.
    """
    base = jnp.asarray(base_key, jnp.uint32)
    salt_b = jnp.broadcast_to(jnp.asarray(salt, jnp.uint32),
                              query_id.shape).astype(jnp.uint32)
    k0, k1 = task_key_pair(base[..., 0], base[..., 1], query_id, hop, salt_b,
                           epoch)
    return jnp.stack([k0, k1], axis=-1)


def stream_key(seed, epoch: int = 0) -> jax.Array:
    """Base key reproducing epoch ``epoch`` of a stream rooted at ``seed``.

    A closed-batch run (``Walker.run``) seeded with ``stream_key(seed, e)``
    samples bit-identical paths to the ``(e, qid)`` occupants of a stream
    rooted at ``seed`` — the reference the streaming soak tests pin.
    Epoch 0 is the root key itself (closed batch == epoch 0).
    """
    base = jax.random.PRNGKey(seed) if jnp.ndim(seed) == 0 else seed
    return base if epoch == 0 else jax.random.fold_in(base, epoch)


def task_uniforms(base_key: jax.Array, query_id: jnp.ndarray, hop: jnp.ndarray,
                  num: int, salt=0, epoch=None) -> jnp.ndarray:
    """(W, num) iid U[0,1) draws, one row per task, derived statelessly."""
    keys = task_fold(base_key, query_id, hop, salt, epoch)
    return bits_to_uniform(key_bits(keys[..., 0], keys[..., 1], num))


def task_bits(base_key: jax.Array, query_id: jnp.ndarray, hop: jnp.ndarray,
              num: int, salt=0, epoch=None) -> jnp.ndarray:
    """(W, num) uint32 random bits per task (for kernels that do their own
    fixed-point arithmetic, mirroring the paper's 64-bit pipeline words)."""
    keys = task_fold(base_key, query_id, hop, salt, epoch)
    return key_bits(keys[..., 0], keys[..., 1], num)

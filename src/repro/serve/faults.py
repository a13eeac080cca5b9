"""Seeded fault injection for the serving stack — the chaos harness.

A fault schedule is a pure function of its :class:`FaultConfig`, exactly
like a traffic workload is of its ``TrafficConfig``: every injection tick,
fault kind and target pick comes out of one seeded
``np.random.default_rng``, so two chaos runs with the same config inject
bit-for-bit the same faults — which is what lets CI assert that recovery
is *bitwise identical* to a fault-free run instead of merely "didn't
crash".

Four fault kinds, covering the serving failure modes the scheduler must
survive (``BatchScheduler`` consumes the injector via ``sched.faults``):

  ``nan``           poison one decode dispatch's logits with NaN for a
                    chosen slot (a numerically-diverged step, an XLA
                    miscompile, a bad reduction) — caught by the on-device
                    finiteness sentinel riding the token readback
  ``page_corrupt``  overwrite one KV pool page a live request reads
                    (``corrupt_mode="nan"``: sentinel-detectable on the
                    next attention read; ``"bitflip"``: a silent bit flip
                    only per-page checksums can catch — the prefix-cache
                    validation path)
  ``alloc_spike``   grab free pages from the pool for a few ticks (a
                    co-tenant's transient burst) — the scheduler must
                    degrade through its normal park/preempt pressure path
                    and recover when the spike releases
  ``hang``          delay one decode dispatch past the watchdog deadline
                    (a stuck collective, a wedged host thread) — the
                    watchdog trips and the victim retries

The injector never touches scheduler internals directly: it hands the
scheduler *due events*; the scheduler applies them through the same
jitted page-edit steps and allocation paths real faults would corrupt,
and defers events that have no applicable target yet (so every scheduled
fault eventually lands while work is live).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Everything a fault schedule is; hash the fields, hash the chaos."""

    seed: int = 0
    horizon_ticks: int = 48       # injection ticks draw from [1, horizon]
    n_nan: int = 1                # poisoned decode dispatches
    n_page_corrupt: int = 1       # corrupted KV pool pages
    n_alloc_spike: int = 1        # transient allocator-exhaustion spikes
    n_hang: int = 1               # delayed (hung) decode dispatches
    corrupt_mode: str = "nan"     # "nan" (sentinel) | "bitflip" (checksum)
    spike_pages: int = 2          # pages a spike grabs (clamped to free)
    spike_ticks: int = 4          # ticks a spike holds them
    hang_s: float = 0.05          # injected dispatch delay (seconds)

    def __post_init__(self):
        if self.corrupt_mode not in ("nan", "bitflip"):
            raise ValueError(
                f"corrupt_mode must be nan|bitflip, got {self.corrupt_mode!r}"
            )
        if self.horizon_ticks < 1:
            raise ValueError("horizon_ticks must be >= 1")


@dataclasses.dataclass
class FaultEvent:
    """One scheduled injection. ``tick`` is advanced when the event is
    deferred (no applicable target yet); ``pick`` selects the victim among
    the applicable candidates (mod their count), so the same schedule hits
    the same targets on a bit-identical rerun. ``request_id`` (tests)
    restricts candidates to one request."""

    kind: str                 # "nan" | "page_corrupt" | "alloc_spike" | "hang"
    tick: int
    pick: int = 0
    pick2: int = 0            # secondary pick (page index within the slot)
    request_id: object = None


def generate_faults(fcfg: FaultConfig) -> list[FaultEvent]:
    """The fault schedule as a pure function of its config."""
    rng = np.random.default_rng(fcfg.seed)
    events: list[FaultEvent] = []
    for kind, n in (("nan", fcfg.n_nan),
                    ("page_corrupt", fcfg.n_page_corrupt),
                    ("alloc_spike", fcfg.n_alloc_spike),
                    ("hang", fcfg.n_hang)):
        for _ in range(max(int(n), 0)):
            events.append(FaultEvent(
                kind=kind,
                tick=int(rng.integers(1, fcfg.horizon_ticks + 1)),
                pick=int(rng.integers(0, 1 << 30)),
                pick2=int(rng.integers(0, 1 << 30)),
            ))
    events.sort(key=lambda e: (e.tick, e.kind, e.pick))
    return events


class FaultInjector:
    """Drives a fault schedule into a ``BatchScheduler`` tick by tick.

    The scheduler polls ``due(tick)`` once per tick and applies each event
    it can; an event with no applicable target (no decoding slot to
    poison, no free page to grab) is handed back via ``defer`` and comes
    due again next tick — a scheduled fault is never silently dropped
    while the injector is attached. ``counters`` records what actually
    landed (the chaos bench artifact and the ``recovery`` stats block
    surface them)."""

    def __init__(self, fcfg: FaultConfig | None = None,
                 events: list[FaultEvent] | None = None):
        self.fcfg = fcfg if fcfg is not None else FaultConfig()
        self.pending: list[FaultEvent] = (
            list(events) if events is not None else generate_faults(self.fcfg)
        )
        self.counters = {
            "nan_injected": 0, "pages_corrupted": 0, "alloc_spikes": 0,
            "hangs": 0, "deferrals": 0,
        }

    def due(self, tick: int) -> list[FaultEvent]:
        """Pop every event scheduled at or before ``tick``."""
        ready = [e for e in self.pending if e.tick <= tick]
        if ready:
            self.pending = [e for e in self.pending if e.tick > tick]
        return ready

    def defer(self, event: FaultEvent, tick: int) -> None:
        """No applicable target this tick: retry the event next tick."""
        event.tick = tick + 1
        self.pending.append(event)
        self.counters["deferrals"] += 1

    @property
    def exhausted(self) -> bool:
        return not self.pending

    def record(self, kind: str) -> None:
        key = {"nan": "nan_injected", "page_corrupt": "pages_corrupted",
               "alloc_spike": "alloc_spikes", "hang": "hangs"}[kind]
        self.counters[key] += 1


# ---------------------------------------------------------------------------
# device-side page edits: corruption, scrubbing, fingerprints
# ---------------------------------------------------------------------------


def _is_paged(path) -> bool:
    # mirrors serve._is_paged_leaf without importing serve (no cycle): the
    # paged attention pools are the only cache leaves with "pages" in their
    # pytree path
    return "pages" in "/".join(
        str(getattr(p, "key", p)) for p in path
    )


_UINT = {2: jnp.uint16, 4: jnp.uint32}
_FLIP = {2: 0x5A5A, 4: 0x5A5A5A5A}


def _edit_leaf(leaf, page, mode):
    """One paged pool leaf (R, Hkv, P, page, hd): rewrite physical ``page``
    in every layer and KV head."""
    if mode == "nan":
        return leaf.at[:, :, page].set(jnp.asarray(jnp.nan, leaf.dtype))
    if mode == "zero":
        return leaf.at[:, :, page].set(jnp.asarray(0, leaf.dtype))
    # "bitflip": XOR a fixed pattern through a bitcast — values stay finite
    # often enough that the NaN sentinel alone cannot catch this; only the
    # per-page checksum path does
    ubits = _UINT[leaf.dtype.itemsize]
    u = jax.lax.bitcast_convert_type(leaf, ubits)
    u = u.at[:, :, page].set(
        u[:, :, page] ^ jnp.asarray(_FLIP[leaf.dtype.itemsize], ubits))
    return jax.lax.bitcast_convert_type(u, leaf.dtype)


def make_page_edit_step(mode: str):
    """Jitted whole-tree page rewrite: corrupt (``nan``/``bitflip``) or
    scrub (``zero``) one physical page across every paged pool leaf;
    non-paged leaves (recurrent state, dense caches) pass through. The
    cache tree is donated — the edit replaces the scheduler's caches the
    same way a decode dispatch does."""

    def edit(caches, page):
        flat = jax.tree_util.tree_flatten_with_path(caches)
        leaves = [
            _edit_leaf(leaf, page, mode) if _is_paged(path) else leaf
            for path, leaf in flat[0]
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(caches), leaves
        )

    return jax.jit(edit, donate_argnums=(0,))


@functools.lru_cache(maxsize=4)
def page_edit_step(mode: str):
    """Process-shared jitted page-edit per mode (page index is traced, so
    one trace covers every page)."""
    return make_page_edit_step(mode)


def make_page_fingerprint_step():
    """Jitted uint32 content fingerprint of one physical page across every
    paged pool leaf (bitcast to integers, wrapping sum — deterministic,
    order-independent within a page, and any single bit flip moves it).
    Cheap enough to run per shared page at prefix-cache attach when
    ``ServeConfig.checksum_pages`` is on."""

    def fingerprint(caches, page):
        acc = jnp.uint32(0)
        for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
            if not _is_paged(path):
                continue
            u = jax.lax.bitcast_convert_type(leaf, _UINT[leaf.dtype.itemsize])
            acc = acc + jnp.sum(u[:, :, page].astype(jnp.uint32),
                                dtype=jnp.uint32)
        return acc

    return jax.jit(fingerprint)


@functools.lru_cache(maxsize=1)
def page_fingerprint_step():
    return make_page_fingerprint_step()


__all__ = [
    "FaultConfig", "FaultEvent", "FaultInjector", "generate_faults",
    "make_page_edit_step", "page_edit_step",
    "make_page_fingerprint_step", "page_fingerprint_step",
]

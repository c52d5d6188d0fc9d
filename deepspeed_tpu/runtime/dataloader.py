"""Data loading utilities.

Reference: ``DeepSpeedDataLoader`` + ``RepeatingLoader``
(``runtime/dataloader.py:41,:17``). On TPU the loader yields *global* batches
(numpy/jnp pytrees); the engine shards them over the dp/sp mesh axes at
dispatch, so there is no per-rank DistributedSampler — every host feeds its
local shard of the global array via ``jax.make_array_from_process_local_data``
in multi-host runs.
"""

from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np


class RepeatingLoader:
    """Wrap an iterator to restart on StopIteration (reference ``:17``)."""

    def __init__(self, loader: Iterable):
        self.loader = loader
        self.data_iter = iter(self.loader)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self.data_iter)
        except StopIteration:
            self.data_iter = iter(self.loader)
            return next(self.data_iter)


class DeepSpeedDataLoader:
    """Minimal batch loader over an indexable dataset of pytrees.

    Supports a ``collate_fn`` and curriculum hooks (``data_pipeline``): when a
    ``curriculum_fn`` is set, it maps ``(epoch, step) -> effective seq length``
    and the loader truncates sequence-like leaves accordingly (legacy
    curriculum learning, reference ``curriculum_scheduler.py:11``).
    """

    def __init__(self, dataset, batch_size: int, shuffle: bool = True, seed: int = 0,
                 collate_fn: Optional[Callable] = None, drop_last: bool = True,
                 curriculum_fn: Optional[Callable] = None,
                 sampler=None):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.collate_fn = collate_fn or _default_collate
        self.drop_last = drop_last
        self.curriculum_fn = curriculum_fn
        # difficulty-driven index selection (data_pipeline
        # DeepSpeedDataSampler — reference deepspeed_io wires its sampler
        # into the torch DataLoader the same way); overrides shuffle order
        self.sampler = sampler
        self.epoch = 0
        self.global_step = 0
        self.batch_in_epoch = 0   # batches YIELDED in the current epoch
        self._resume_offset = 0   # batches to fast-forward on next __iter__
        n = len(dataset)
        self.len = n // batch_size if drop_last else (n + batch_size - 1) // batch_size

    def __len__(self):
        return self.len

    # -- resumable data stream (recorded in snapshot meta) ---------------
    def state_dict(self) -> dict:
        """The loader's position: restoring it into a FRESH loader over the
        same dataset/seed and iterating reproduces the exact batch sequence
        an uninterrupted run would have yielded from here."""
        return {"epoch": self.epoch, "batch_in_epoch": self.batch_in_epoch,
                "seed": self.seed, "global_step": self.global_step}

    def load_state_dict(self, state: dict) -> None:
        self.epoch = int(state["epoch"])
        self.seed = int(state.get("seed", self.seed))
        self.global_step = int(state.get("global_step", 0))
        self.batch_in_epoch = 0
        self._resume_offset = int(state.get("batch_in_epoch", 0))

    def __iter__(self) -> Iterator[Any]:
        n = len(self.dataset)
        start, self._resume_offset = self._resume_offset, 0
        if self.sampler is None:
            order = np.arange(n)
            if self.shuffle:
                rng = np.random.default_rng(self.seed + self.epoch)
                rng.shuffle(order)
        elif start:
            # curriculum sampler: fast-forward by consuming (and discarding)
            # the skipped draws — the sampler's stream is deterministic, so
            # position IS the resume state
            for _ in range(start):
                self.sampler.next_batch()
        self.batch_in_epoch = start
        for i in range(start, self.len):
            if self.sampler is not None:
                idx = self.sampler.next_batch()
            else:
                idx = order[i * self.batch_size:(i + 1) * self.batch_size]
            batch = self.collate_fn([self.dataset[int(j)] for j in idx])
            if self.curriculum_fn is not None:
                seqlen = int(self.curriculum_fn(self.epoch, self.global_step))
                batch = _truncate_seq(batch, seqlen)
            self.global_step += 1
            self.batch_in_epoch = i + 1
            yield batch
        self.epoch += 1
        self.batch_in_epoch = 0


class PrefetchLoader:
    """Double-buffered device prefetch over any batch iterator.

    The TPU input-pipeline analogue of the reference dataloader's pinned
    memory + worker prefetch (``DeepSpeedDataLoader(pin_memory=...,
    num_local_io_workers=...)``): while step ``t`` computes, batch ``t+1`` is
    already being transferred host->device asynchronously (``jax.device_put``
    returns immediately; the copy overlaps the running computation). With a
    sharding, leaves land directly in their dispatch layout so the engine's
    jit does no re-placement.

    ``depth`` batches are kept in flight (2 = classic double buffering).

    Re-iterability and ``len()`` follow the WRAPPED loader: a list or
    ``DeepSpeedDataLoader`` gives a sized, re-iterable prefetcher; a one-shot
    generator gives a one-shot prefetcher whose ``len()`` raises (same
    ``TypeError`` the generator itself would).
    """

    def __init__(self, loader: Iterable, sharding=None, depth: int = 2):
        self.loader = loader
        self.sharding = sharding
        self.depth = max(1, int(depth))
        self._inflight = 0  # batches drawn from the wrapped loader, not yet yielded

    # -- resumable data stream: delegate, corrected for prefetch depth ---
    def state_dict(self) -> dict:
        """Wrapped-loader state at the CONSUMED position: batches sitting in
        the prefetch queue were drawn but never reached the trainer, so the
        wrapped position is rolled back by the in-flight count (wrapping an
        epoch boundary when needed)."""
        inner = getattr(self.loader, "state_dict", None)
        if inner is None:
            raise TypeError("PrefetchLoader wraps a loader without "
                            "state_dict(); wrap a DeepSpeedDataLoader for "
                            "resumable iteration")
        state = dict(inner())
        bi = int(state.get("batch_in_epoch", 0)) - self._inflight
        gs = int(state.get("global_step", 0)) - self._inflight
        if bi < 0:
            state["epoch"] = int(state["epoch"]) - 1
            bi += len(self.loader)
        state["batch_in_epoch"] = bi
        state["global_step"] = max(0, gs)
        return state

    def load_state_dict(self, state: dict) -> None:
        self.loader.load_state_dict(state)
        self._inflight = 0

    def _put(self, batch):
        if self.sharding is None:
            return jax.tree.map(jax.device_put, batch)
        return jax.tree.map(lambda x: jax.device_put(x, self.sharding), batch)

    def __iter__(self):
        import collections

        queue = collections.deque()
        it = iter(self.loader)
        self._inflight = 0
        try:
            for _ in range(self.depth):
                queue.append(self._put(next(it)))
                self._inflight += 1
        except StopIteration:
            pass
        while queue:
            out = queue.popleft()
            try:
                queue.append(self._put(next(it)))
                self._inflight += 1
            except StopIteration:
                pass
            self._inflight -= 1
            yield out

    def __len__(self):
        try:
            return len(self.loader)
        except TypeError:
            raise TypeError("PrefetchLoader wraps an unsized iterator; "
                            "wrap a sized loader (list, DeepSpeedDataLoader) "
                            "if len() is needed") from None


def _default_collate(items):
    first = items[0]
    if isinstance(first, dict):
        return {k: np.stack([it[k] for it in items]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(np.stack([it[i] for it in items]) for i in range(len(first)))
    return np.stack(items)


def _truncate_seq(batch, seqlen: int):
    def trunc(x):
        if hasattr(x, "ndim") and x.ndim >= 2 and x.shape[1] > seqlen:
            return x[:, :seqlen]
        return x

    return jax.tree.map(trunc, batch)

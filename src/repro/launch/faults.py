"""Deterministic fault injection for the serving tier (DESIGN.md §14).

The chaos suite (``tests/test_faults.py``, ``benchmarks/bench_serve.py``)
never monkeypatches server internals: :class:`FaultInjector` is installed
through the three hook seams ``CNNServer`` exposes via its ``faults=``
parameter, and every injector is deterministic — poison targets are
registered by content digest, slow/kill faults fire on configured
dispatch ordinals — so a chaos run replays exactly.

Hook seams (called by the server's threads: ``post_serve`` where a
launched batch is fetched, the others where batches are launched):

- ``on_tick(n_items)`` — once per dispatcher loop iteration that has
  work to process, *before* any batching. Raising here simulates a
  dispatcher **crash** (not a dispatch error): the server's supervision
  must fail every pending future with ``ServerCrashed``. A ``kills``
  budget bounds how many times the kill fires, so a supervised restart
  (DESIGN.md §15) can recover deterministically instead of crash-looping.
- ``on_restart(restarts)`` — called by the :class:`Supervisor` after it
  brings the dispatcher back up; the injector records the count so chaos
  tests can assert the restart actually happened through supervision.
- ``pre_bucket(bucket)`` — immediately before a *compiled* bucket plan
  dispatch (never before the ref fallback). ``fail_bucket`` registers a
  persistent per-bucket backend fault here: the compiled path for that
  bucket keeps raising until ``heal_bucket``, which is exactly the shape
  of a broken pallas lowering — the server must demote the bucket to its
  ref fallback and a later recovery probe re-promotes once healed.
- ``pre_dispatch(pendings)`` — before a batch is assembled. Raising
  :class:`FaultInjected` here simulates a **plan exception**; because the
  server re-runs the hook on every bisected sub-batch, a registered
  poison request re-raises all the way down to its lone dispatch, which
  is exactly how a real deterministic poison input behaves.
- ``pre_serve(pendings, xb) -> xb`` — after host assembly, before the
  bucket dispatch. This seam injects **slow plans** (``slow_s`` sleep,
  driving deadline/overload scenarios).
- ``post_serve(pendings, y) -> y`` — after the bucket dispatch, before
  per-request scatter. This seam injects **NaN activations** into the
  logits rows of nan-poisoned requests. It has to live *past* the
  datapath: NaN request *inputs* are already rejected at admission, and
  a NaN smuggled into the batch would be clipped finite by the int8
  requantize chain — so a numeric fault is simulated where one would
  surface, and only the server's per-request output check can isolate
  it.

:func:`bad_input` builds the malformed *request* side of the suite:
wrong-shape / wrong-dtype / non-finite arrays that admission validation
(``validate_request``) must reject alone. :func:`corrupt_checkpoint`
writes targeted, deterministic damage (bit-flip / truncation / manifest
edit / missing file) into an on-disk checkpoint so the §15 integrity
verification is exercised against real corruption, not mocks.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import time
from typing import List, Optional

import numpy as np


class FaultInjected(RuntimeError):
    """The typed error every injector raises — chaos tests assert that
    exactly the poisoned future carries exactly this."""


def bad_input(kind: str, sample_shape, *, dtype=np.float32, n: int = 1,
              seed: int = 0) -> np.ndarray:
    """A deterministic malformed request for admission-validation tests.

    ``kind``: ``'shape'`` (one trailing dim off by one), ``'rank'``
    (missing a dim), ``'dtype'`` (float64 instead of the spec dtype),
    ``'nan'`` / ``'inf'`` (spec-shaped but non-finite). All are built
    from a seeded RNG so reruns submit byte-identical poison.
    """
    rng = np.random.default_rng(seed)
    shape = (n,) + tuple(sample_shape)
    if kind == "shape":
        shape = shape[:-1] + (shape[-1] + 1,)
        return rng.standard_normal(shape).astype(dtype)
    if kind == "rank":
        return rng.standard_normal(shape[:-1]).astype(dtype)
    if kind == "dtype":
        return rng.standard_normal(shape).astype(
            np.float64 if np.dtype(dtype) != np.float64 else np.float32)
    if kind in ("nan", "inf"):
        x = rng.standard_normal(shape).astype(dtype)
        x[tuple(0 for _ in shape)] = np.nan if kind == "nan" else np.inf
        return x
    raise ValueError(f"unknown bad_input kind {kind!r}")


def corrupt_checkpoint(ckpt_dir, *, step: Optional[int] = None,
                       mode: str = "flip", seed: int = 0) -> pathlib.Path:
    """Write targeted, deterministic damage into an on-disk checkpoint
    (the §15 integrity corpus). Returns the damaged step directory.

    ``mode``:
      - ``'flip'``      — flip one seeded byte in ``arrays.npz`` (a leaf
        or archive byte: either way the sha256 record catches it),
      - ``'truncate'``  — cut ``arrays.npz`` to half length (torn write),
      - ``'manifest'``  — edit a manifest field without re-digesting,
      - ``'missing'``   — delete ``arrays.npz`` entirely.

    All four must surface as ``CorruptCheckpointError`` at restore —
    never silent garbage.
    """
    from repro.checkpoint.store import latest_step

    ckpt_dir = pathlib.Path(ckpt_dir)
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    d = ckpt_dir / f"step_{step:08d}"
    arrays = d / "arrays.npz"
    if mode == "flip":
        raw = bytearray(arrays.read_bytes())
        # skip the zip local-file header; flip inside the payload
        i = 64 + np.random.default_rng(seed).integers(max(len(raw) - 128, 1))
        raw[int(i)] ^= 0xFF
        arrays.write_bytes(bytes(raw))
    elif mode == "truncate":
        raw = arrays.read_bytes()
        arrays.write_bytes(raw[: len(raw) // 2])
    elif mode == "manifest":
        mf = d / "manifest.json"
        manifest = json.loads(mf.read_text())
        manifest["n_leaves"] = int(manifest.get("n_leaves", 0)) + 1
        mf.write_text(json.dumps(manifest))  # digest left stale on purpose
    elif mode == "missing":
        arrays.unlink()
    else:
        raise ValueError(f"unknown corrupt_checkpoint mode {mode!r}")
    return d


def _digest(x) -> str:
    a = np.ascontiguousarray(np.asarray(x))
    h = hashlib.sha1()
    h.update(str(a.shape).encode())
    h.update(str(a.dtype).encode())
    h.update(a.tobytes())
    return h.hexdigest()


class FaultInjector:
    """Deterministic hook bundle for ``CNNServer(faults=...)``.

    >>> inj = FaultInjector(slow_s=0.05)
    >>> poison = inj.poison(xpool[2:3])           # dispatch-time raise
    >>> nanpoison = inj.poison(xpool[3:4], mode="nan")  # NaN activations
    >>> srv = CNNServer(plan_set, faults=inj)

    Parameters
    ----------
    slow_s:
        Sleep injected into every ``pre_serve`` (a uniformly slow plan —
        drives deadline-expiry and overload scenarios).
    kill_after_dispatches:
        After this many dispatches have run, the next dispatcher tick
        with pending work raises (a dispatcher kill, exercising
        ``ServerCrashed`` supervision). ``None`` disables.
    kills:
        Budget on how many dispatcher kills fire in total (``None`` =
        unlimited, the §14 behavior). ``kills=1`` models a transient
        crash a supervised restart recovers from; unlimited models a
        crash loop the circuit breaker must arrest.
    """

    def __init__(self, *, slow_s: float = 0.0,
                 kill_after_dispatches: Optional[int] = None,
                 kills: Optional[int] = None):
        self.slow_s = float(slow_s)
        self.kill_after_dispatches = kill_after_dispatches
        self.kills = kills
        self.kills_fired = 0         # dispatcher kills delivered
        self.restarts = 0            # supervisor restarts observed
        self.dispatches = 0          # pre_serve invocations observed
        self.faults_fired = 0        # poison/kill raises delivered
        self.bucket_faults_fired = 0  # pre_bucket raises delivered
        self._poison = {}            # content digest -> 'raise' | 'nan'
        self._bad_buckets = {}       # bucket -> remaining raises (None=inf)

    # ------------------------------------------------------ poison API
    def poison(self, x, mode: str = "raise"):
        """Register ``x`` (one request's array) as poison and return it
        unchanged. ``mode='raise'`` makes any batch containing it fail at
        ``pre_dispatch`` (a plan exception); ``mode='nan'`` corrupts its
        logits rows with NaN at ``post_serve`` (NaN activations — past
        admission and the int8 datapath, so only the server's
        per-request output check can isolate it)."""
        if mode not in ("raise", "nan"):
            raise ValueError(f"mode must be 'raise' or 'nan', got {mode!r}")
        self._poison[_digest(x)] = mode
        return x

    def is_poisoned(self, x, mode: str = "raise") -> bool:
        return self._poison.get(_digest(x)) == mode

    # ----------------------------------------------- per-bucket faults
    def fail_bucket(self, bucket: int, *, times: Optional[int] = None):
        """Register a persistent backend fault on one bucket's *compiled*
        plan: every ``pre_bucket(bucket)`` raises until ``times`` raises
        have fired (``None`` = until :meth:`heal_bucket`). The ref
        fallback path never consults this seam, which is the point — a
        broken pallas lowering doesn't break the interpreter path."""
        self._bad_buckets[int(bucket)] = times

    def heal_bucket(self, bucket: int) -> None:
        """Clear a bucket's persistent fault (the backend was fixed):
        the server's next recovery probe on the compiled path succeeds
        and re-promotes the bucket."""
        self._bad_buckets.pop(int(bucket), None)

    # ------------------------------------------------- server hook seams
    def on_tick(self, n_items: int) -> None:
        if (self.kill_after_dispatches is not None
                and self.dispatches >= self.kill_after_dispatches
                and n_items > 0
                and (self.kills is None or self.kills_fired < self.kills)):
            self.faults_fired += 1
            self.kills_fired += 1
            raise FaultInjected(
                f"dispatcher killed after {self.dispatches} dispatches")

    def on_restart(self, restarts: int) -> None:
        """Supervisor seam: records each completed restart (chaos tests
        assert the recovery path really went through supervision)."""
        self.restarts = int(restarts)

    def pre_bucket(self, bucket: int) -> None:
        left = self._bad_buckets.get(int(bucket), 0)
        if left is None or left > 0:
            if left is not None:
                self._bad_buckets[int(bucket)] = left - 1
                if left - 1 <= 0:
                    self._bad_buckets.pop(int(bucket), None)
            self.faults_fired += 1
            self.bucket_faults_fired += 1
            raise FaultInjected(
                f"backend fault on compiled bucket-{bucket} dispatch")

    def pre_dispatch(self, pendings: List) -> None:
        hit = [p for p in pendings if self.is_poisoned(p.x, "raise")]
        if hit:
            self.faults_fired += 1
            raise FaultInjected(
                f"plan exception: {len(hit)} poisoned request(s) in a "
                f"batch of {len(pendings)}")

    def pre_serve(self, pendings: List, xb: np.ndarray) -> np.ndarray:
        self.dispatches += 1
        if self.slow_s > 0:
            time.sleep(self.slow_s)
        return xb

    def post_serve(self, pendings: List, y: np.ndarray) -> np.ndarray:
        off = 0
        for p in pendings:
            if self.is_poisoned(p.x, "nan"):
                self.faults_fired += 1
                y = np.array(y)  # copy-on-poison: never mutate shared output
                y[off : off + p.n] = np.nan
            off += p.n
        return y

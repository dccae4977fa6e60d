"""Serving: text-in/tokens-out continuous batching over a row program.

The serving path closes the train/serve loop: requests arrive as raw
abstract text, are encoded by the *same* compiled plan the training
executors run (a :class:`~repro.runtime.row_program.RowProgram`, passed in
by the caller), and flow into micro-batched continuous batching — fixed
decode slots with block-prefill refill, fed by a bounded admission queue
that sheds load on arrival, with a fixed-slot :class:`RingCache` fronting
repeated prompts.

Layers, bottom up:

* ``make_serve_step`` — the one-token greedy decode step (jit'd).
* ``_programs`` — the step and prefill programs, made once per model
  instance on its first serve and reused by every later call on it.
* ``_continuous_decode`` — the slot driver: fixed decode slots, refill on
  completion from a ``next_item`` callback (continuous batching in its
  simplest correct form, unchanged from the original loop).
* ``serve_requests`` — the legacy pre-tokenized entry point
  (:class:`Request` carries an int32 prompt array), kept for
  ``launch/serve.py`` and direct callers.
* ``serve_text`` — the end-to-end entry point: :class:`TextRequest` in,
  token lists out, with an :class:`AdmissionQueue`, per-request
  preprocessing through the row program, ring-cache hits, and a
  :class:`ServeStats` ledger (admission/shed/filter counters, cache
  accounting, the row program's, prefill's, decode steps' and compiles'
  time, per-request latency, first-token time and token gaps).

The row program, each prefill and each decode step run in program spans
(``serve.row_program``, ``serve.prefill``, ``serve.decode_step``, see
:mod:`repro.spans`): they land on a profiler trace and add their time to
the ledger.

Contract (linter rule R005): this module is the serve hot path — it must
never import the shard/shm/pool machinery (``core.executor``,
``core.async_loader``, ``repro.distributed``, ``multiprocessing``). The
row program arrives as an argument; anything it needs it carries.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.moe import tally_paths
from ..spans import span

PAD_ID = 0


def make_serve_step(model):
    """serve_step(params, tokens (b,1), state, pos) -> (next_tokens, logits, state).

    Greedy sampling on-device: the returned tokens feed the next step
    directly, keeping decode a device-side loop with O(1) host traffic.
    """

    def serve_step(params, tokens, state, pos):
        logits, state = model.decode_step(params, tokens, state, pos)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, state

    return serve_step


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new: int = 16


@dataclass
class TextRequest:
    """A raw serving request: abstract text (or a field dict for multi-field
    plans), encoded through the row program at admission time."""

    uid: int
    text: str | Mapping[str, Any]
    max_new: int = 16


class AdmissionQueue:
    """Bounded FIFO admission queue: load is shed on *arrival* (``offer``
    returns False and counts a rejection when full), so an overloaded
    server degrades by refusing new work deterministically instead of
    queueing unboundedly. Thread-safe: producers may offer from request
    threads while the decode loop pops."""

    def __init__(self, maxsize: int = 16):
        if maxsize < 1:
            raise ValueError(f"queue size must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self.admitted = 0
        self.rejected = 0
        self._items: deque = deque()
        self._lock = threading.Lock()

    def offer(self, item: Any) -> bool:
        with self._lock:
            if len(self._items) >= self.maxsize:
                self.rejected += 1
                return False
            self._items.append(item)
            self.admitted += 1
            return True

    def pop(self) -> Any | None:
        with self._lock:
            return self._items.popleft() if self._items else None

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


class RingCache:
    """Fixed-slot FIFO response cache fronting repeated prompts.

    The decode path already reuses state through the model's sliding-window
    ring buffer (``test_ring_cache.py``); this is the request-level analogue
    — a fixed number of slots, overwrite-oldest on overflow — so a repeated
    prompt skips preprocessing *and* decoding entirely. Keys should bind
    the row-program fingerprint (see :func:`serve_text`), making a stale
    hit across plan or vocab changes structurally impossible."""

    def __init__(self, slots: int = 64):
        if slots < 1:
            raise ValueError(f"cache slots must be >= 1, got {slots}")
        self.slots = slots
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._data: OrderedDict = OrderedDict()

    def get(self, key: Any) -> list[int] | None:
        hit = self._data.get(key)
        if hit is None:
            self.misses += 1
            return None
        self.hits += 1
        return list(hit)

    def put(self, key: Any, value: Sequence[int]) -> None:
        if key in self._data:
            self._data[key] = list(value)
            return
        if len(self._data) >= self.slots:
            self._data.popitem(last=False)  # FIFO: overwrite-oldest
            self.evictions += 1
        self._data[key] = list(value)

    def __len__(self) -> int:
        return len(self._data)


@dataclass
class ServeStats:
    """One serve run's ledger, which may span several ``serve_text`` calls.

    Counters: admission, shed, filter, served, ring-cache hits and misses.
    Time, each the sum of its spans: ``preprocess_s`` the row program
    (``serve.row_program``), ``prefill_s`` each prompt's prefill through its
    first token on the host (``serve.prefill``), ``decode_s`` each decode
    step through its token on the host (``serve.decode_step``).
    ``compiles`` and ``compile_s``: the programs traced, lowered, and
    compiled or loaded from the compilation cache while a call ran, and
    their time. ``program_builds`` and ``program_reuses``: per call,
    whether the model's step and prefill programs had to be made or were
    found on the model. ``moe_paths``: per program (``step``,
    ``prefill``), the expert paths its MoE layers took where the model's
    programs were traced (``reached``, ``ragged``; see
    :func:`repro.models.moe.tally_paths`), empty for a model without MoE.
    ``state_bytes``: the bytes of one slot's decode
    state (its cache), counted from the state's shapes when the slots'
    states are made. Per request (by uid): ``latency_s`` from the admission
    offer to the final token, ``first_token_s`` from the offer to the
    first token; ``token_gaps_s`` holds, for every later token, its gap
    since the same request's previous token."""

    admitted: int = 0
    rejected: int = 0
    filtered: int = 0
    served: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    preprocess_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    compiles: int = 0
    compile_s: float = 0.0
    program_builds: int = 0
    program_reuses: int = 0
    moe_paths: dict[str, tuple[str, ...]] = field(default_factory=dict)
    state_bytes: int = 0
    latency_s: dict[int, float] = field(default_factory=dict)
    first_token_s: dict[int, float] = field(default_factory=dict)
    token_gaps_s: list[float] = field(default_factory=list)


# The compile events of jax.monitoring, as their time spans. Traces nest:
# jitted functions called inside another (jnp's own among them) trace inside
# its trace, and a few trace again inside its lowering. So a span that holds
# spans already counted adds only the time outside them, and ``compile_s``
# is the union of the spans. The backend compile holds the persistent-cache
# read, so a cache load counts once, as one of ``compiles``.
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class _CompileLedger(threading.local):
    """Adds JAX's compile events to the ServeStats of the ``serve_text``
    call running on the thread that compiles (``stats``; None outside a
    call). ``jax.monitoring`` listeners are process-wide, so one listener,
    registered at import, serves every call."""

    def __init__(self):
        self.stats: ServeStats | None = None
        self.spans: list[tuple[float, float]] = []  # outermost spans counted

    def event(self, event: str, start: float, end: float, **_kw) -> None:
        st = self.stats
        if st is None or event not in _COMPILE_EVENTS:
            return
        inner = [(s, e) for s, e in self.spans if start <= s and e <= end]
        if inner:
            self.spans = [x for x in self.spans if x not in inner]
        self.spans.append((start, end))
        st.compile_s += (end - start) - sum(e - s for s, e in inner)
        st.compiles += event == _COMPILE_EVENTS[2]


_compile_ledger = _CompileLedger()
jax.monitoring.register_event_time_span_listener(_compile_ledger.event)


def _tallied(fn, paths: set):
    """``fn``, adding the MoE expert paths each of its traces takes to
    ``paths`` (the name stays ``fn``'s, and so does the program's)."""

    @functools.wraps(fn)
    def traced(*args):
        with tally_paths() as tally:
            out = fn(*args)
        paths.update(tally)
        return out

    return traced


def _programs(model, stats: ServeStats | None):
    """The model's jitted decode step and prefill, made on its first serve
    and kept on the instance, so every later call on it reuses them and
    JAX's own cache (keyed on shapes and dtypes) serves each prompt length,
    ``max_seq`` and ``cache_dtype`` it has seen. As an attribute they live
    and die with the model (their closures hold it, so nothing outside the
    model may hold them). ``params`` stay arguments, so one program serves
    any weights of the same shapes. The third item gathers, per program,
    the MoE expert paths its traces took."""
    programs = getattr(model, "_serve_programs", None)
    built = programs is None
    if built:
        paths = {"step": set(), "prefill": set()}
        programs = (jax.jit(_tallied(make_serve_step(model), paths["step"])),
                    jax.jit(_tallied(model.decode_step, paths["prefill"])), paths)
        model._serve_programs = programs
    if stats is not None:
        stats.program_builds += built
        stats.program_reuses += not built
    return programs


def _continuous_decode(
    model,
    params,
    next_item: Callable[[], tuple[int, np.ndarray, int] | None],
    on_done: Callable[[int, list[int]], None],
    *,
    slots: int = 4,
    max_seq: int = 128,
    eos_id: int = 2,
    cache_dtype=jnp.float32,
    stats: ServeStats | None = None,
    on_token: Callable[[int, int], None] | None = None,
) -> None:
    """The continuous-batching slot driver: fixed decode slots; a finished
    slot refills immediately from ``next_item`` (block prefill, one slot at
    a time, per-slot position tracking). ``next_item`` returns
    ``(uid, prompt, max_new)`` or None when drained; ``on_done`` receives
    each request's generated tokens. Each prefill and decode step is a
    span whose time goes to ``stats.prefill_s`` / ``stats.decode_s`` when
    ``stats`` is given; ``on_token(uid, index)`` is called as each token
    reaches the host (index 0 is the prefill's)."""
    step, prefill, paths = _programs(model, stats)

    # one independent state per slot (batch=1) so refills don't disturb others
    states = [model.init_decode_state(1, max_seq, cache_dtype) for _ in range(slots)]
    if stats is not None:
        stats.state_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states[0]))
    active: list[dict | None] = [None] * slots
    last_tok = [None] * slots

    def fill(slot: int) -> None:
        item = next_item()
        if item is None:
            active[slot] = None
            return
        uid, prompt, max_new = item
        states[slot] = model.init_decode_state(1, max_seq, cache_dtype)
        with span("serve.prefill", stats, "prefill_s"):
            logits, states[slot] = prefill(
                params, jnp.asarray(prompt[None]), states[slot], jnp.int32(0)
            )
            nxt = int(jnp.argmax(logits[0, -1]))
        if on_token is not None:
            on_token(uid, 0)
        active[slot] = {"uid": uid, "max_new": max_new, "pos": len(prompt), "out": [nxt]}
        last_tok[slot] = nxt

    for s in range(slots):
        fill(s)

    while any(a is not None for a in active):
        for s in range(slots):
            a = active[s]
            if a is None:
                continue
            done = (
                last_tok[s] == eos_id
                or len(a["out"]) >= a["max_new"]
                or a["pos"] + 1 >= max_seq
            )
            if done:
                on_done(a["uid"], a["out"])
                fill(s)
                continue
            with span("serve.decode_step", stats, "decode_s"):
                toks = jnp.full((1, 1), last_tok[s], jnp.int32)
                nxt, _, states[s] = step(params, toks, states[s], jnp.int32(a["pos"]))
                last_tok[s] = int(nxt[0, 0])
            if on_token is not None:
                on_token(a["uid"], len(a["out"]))
            a["out"].append(last_tok[s])
            a["pos"] += 1
    if stats is not None:
        stats.moe_paths = {name: tuple(sorted(took)) for name, took in paths.items() if took}


def serve_requests(
    model,
    params,
    requests: Sequence[Request],
    *,
    slots: int = 4,
    max_seq: int = 128,
    eos_id: int = 2,
    cache_dtype=jnp.float32,
) -> dict[int, list[int]]:
    """Continuous-batching driver over pre-tokenized prompts (the legacy
    entry point; ``serve_text`` is the raw-text path)."""
    queue = deque(requests)
    results: dict[int, list[int]] = {}

    def next_item():
        if not queue:
            return None
        req = queue.popleft()
        return req.uid, req.prompt, req.max_new

    def on_done(uid: int, out: list[int]) -> None:
        results[uid] = out

    _continuous_decode(
        model,
        params,
        next_item,
        on_done,
        slots=slots,
        max_seq=max_seq,
        eos_id=eos_id,
        cache_dtype=cache_dtype,
    )
    return results


def _cache_key(row_program, text: str | Mapping[str, Any]) -> tuple:
    """Bind the response cache to this exact plan + vocabulary: any change
    to the compiled steps or the fitted tokenizer changes the fingerprint,
    so a redeploy can never serve stale cached completions."""
    if isinstance(text, Mapping):
        text_key: Any = tuple(sorted((str(k), str(v)) for k, v in text.items()))
    else:
        text_key = text
    return (row_program.fingerprint, text_key)


def serve_text(
    model,
    params,
    row_program,
    requests: Sequence[TextRequest],
    *,
    slots: int = 4,
    max_seq: int = 128,
    queue_size: int = 16,
    eos_id: int = 2,
    prompt_output: str | None = None,
    cache: RingCache | None = None,
    cache_dtype=jnp.float32,
    stats: ServeStats | None = None,
) -> dict[int, list[int]]:
    """End-to-end serving: raw text in, generated token lists out.

    Each request is checked against the ring cache at admission (key =
    row-program fingerprint + text; a hit completes immediately), then
    offered to the bounded admission queue — a full queue sheds the
    request on arrival (no entry in the result dict; counted in
    ``stats.rejected``). Admitted requests are preprocessed through the
    row program when a decode slot picks them up: the prompt is
    ``prompt_output``'s non-pad prefix (default: the program's first token
    output), clamped to ``max_seq - 1``. A request whose row the plan
    filters out — or that encodes to an empty prompt — is answered with
    ``[]`` and counted in ``stats.filtered``; it never occupies a slot.

    ``stats`` (a :class:`ServeStats`) receives counters, the time of the
    row program, prefills, decode steps and compiles, and per-uid latency,
    first-token time and token gaps.
    """
    st = stats if stats is not None else ServeStats()
    out_name = prompt_output or row_program.output_names[0]
    queue = AdmissionQueue(queue_size)
    results: dict[int, list[int]] = {}
    offered_at: dict[int, float] = {}
    keys: dict[int, tuple] = {}
    token_at: dict[int, float] = {}

    for req in requests:
        key = _cache_key(row_program, req.text)
        now = time.perf_counter()
        if cache is not None:
            hit = cache.get(key)
            if hit is not None:
                results[req.uid] = hit
                st.cache_hits += 1
                st.served += 1
                st.latency_s[req.uid] = time.perf_counter() - now
                continue
            st.cache_misses += 1
        if queue.offer(req):
            offered_at[req.uid] = now
            keys[req.uid] = key
        else:
            st.rejected += 1
    st.admitted += queue.admitted  # += so one ledger can span serve waves

    def next_item():
        while True:
            req = queue.pop()
            if req is None:
                return None
            with span("serve.row_program", st, "preprocess_s"):
                encoded = row_program(req.text)
            prompt = None if encoded is None else encoded[out_name][0]
            if prompt is not None:
                prompt = prompt[prompt != PAD_ID][: max_seq - 1]
            if prompt is None or prompt.size == 0:
                # Filtered by the plan (or cleaned to nothing): answer
                # empty immediately, don't burn a decode slot.
                results[req.uid] = []
                st.filtered += 1
                st.latency_s[req.uid] = time.perf_counter() - offered_at[req.uid]
                continue
            return req.uid, np.asarray(prompt, dtype=np.int32), req.max_new

    def on_token(uid: int, index: int) -> None:
        now = time.perf_counter()
        if index == 0:
            st.first_token_s[uid] = now - offered_at[uid]
        else:
            st.token_gaps_s.append(now - token_at[uid])
        token_at[uid] = now

    def on_done(uid: int, out: list[int]) -> None:
        results[uid] = out
        st.served += 1
        st.latency_s[uid] = time.perf_counter() - offered_at[uid]
        if cache is not None:
            cache.put(keys[uid], out)

    _compile_ledger.stats, _compile_ledger.spans = st, []
    try:
        _continuous_decode(
            model,
            params,
            next_item,
            on_done,
            slots=slots,
            max_seq=max_seq,
            eos_id=eos_id,
            cache_dtype=cache_dtype,
            stats=st,
            on_token=on_token,
        )
    finally:
        _compile_ledger.stats = None
    return results

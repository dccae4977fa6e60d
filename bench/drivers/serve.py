"""Serving driver: open-loop text requests into ``serve_text`` in waves.

Set-up fits the summarizer's vocabulary on a corpus made from the seed,
lowers the serving half of the plan to a row program, makes the model's
weights on the device from the seed, draws the traffic, and warms every
prompt length the traffic will use through ``serve_text`` itself.

The window offers requests on their fixed schedule. Each wave is one
``serve_text`` call with every request that is due when it starts;
requests that come due during a wave wait for the next one. A request's
latency runs from when it was due to its last token:
``wave_start - due + ServeStats.latency_s[uid]``. Requests the admission
queue sheds are failed.

``check`` compares, after the window: every admitted request was
answered with a whole answer; for a seeded sample of the answered
requests (the one with the most served tokens among them), the row
program's token row equals the plain reference of the plan, and the
served tokens lie close to the reference model's best at each position.

Everything that depends on the model comes from the plain reference
module the configuration names (``"reference": "<module>"``, the file
``bench/reference/<module>.py``), and from nowhere else:

* ``PROGRAM_KEYS``: configuration key -> attribute path on the program's
  ``ArchConfig`` (dotted paths reach nested groups) that must equal it;
* ``init_params(key, cfg)``: the seeded weights in the program's layout;
* ``request_flops(cfg, prompt_len, generated)``: the matrix operations
  of one served request;
* ``served_gaps(params, prompts, served, cfg, *, control=False)``: the
  gaps of the served tokens below the reference's best logits (with
  ``control``, also those of the reference at the next lower precision).

A new model architecture is served by a configuration file and a
reference module, with no edit here.
"""

from __future__ import annotations

import functools
import importlib
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus
from bench.drivers.train import prng_key
from bench.reference import text as ref_text

NONE_READ = 1e30  # a reading where nothing could be compared: fails every limit
ARRIVALS = 20240613  # the one order of the arrival gaps, the same for every seed
REFERENCES = Path(__file__).resolve().parents[1] / "reference"


def reference(cfg: dict):
    """The plain reference module that the configuration names."""
    name = cfg.get("reference")
    if name is None:
        raise KeyError(
            f"the serving configuration of {cfg.get('program_config')!r} has no \"reference\" key: its file "
            f"in bench/configs/ must name its plain reference, \"reference\": \"<module>\" for "
            f"bench/reference/<module>.py"
        )
    module = f"bench.reference.{name}"
    if str(name).isidentifier():
        try:
            return importlib.import_module(module)
        except ModuleNotFoundError as e:
            if e.name != module:
                raise
    there = sorted(p.stem for p in REFERENCES.glob("*.py") if p.stem != "__init__")
    raise KeyError(
        f"the configuration names reference {name!r}, but there is no bench/reference/{name}.py; "
        f"there are {there}"
    )


def schedule(traffic: dict, seconds: float, seed: int):
    """(due offset s, text, max_new) of every request of the window.

    The amount of work is the same for every seed: the request count is
    ``rate x seconds``; the ``n - 1`` gaps between arrivals are the
    exponential distribution's quantiles at (i + 1/2)/(n - 1) with mean
    ``1 / rate``, in one fixed order (``ARRIVALS``), so every run offers
    the same arrival times over the same span; word counts and
    ``max_new`` are spread evenly over their ranges. The seed orders the
    sizes over the arrivals and writes the words."""
    rate = traffic["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    qa = (np.arange(n - 1) + 0.5) / max(n - 1, 1)
    gaps = np.random.default_rng(ARRIVALS).permutation(-np.log1p(-qa) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
    q = (np.arange(n) + 0.5) / n
    lo, hi = traffic["abstract_words"]
    words = rng.permutation(lo + np.floor(q * (hi - lo + 1)).astype(int))
    lo, hi = traffic["max_new"]
    max_new = rng.permutation(lo + np.floor(q * (hi - lo + 1)).astype(int))
    texts = corpus.abstract_texts(seed + traffic["text_seed_offset"], words)
    return [(float(d), t, int(m)) for d, t, m in zip(due, texts, max_new)]


class RecordingRowProgram:
    """The row program under a span, keeping each request's token row."""

    def __init__(self, program, annotate):
        self.program = program
        self.annotate = annotate
        self.fingerprint = program.fingerprint
        self.output_names = program.output_names
        self.rows: dict = {}

    def __call__(self, text):
        with self.annotate("row_program"):
            out = self.program(text)
        self.rows[text] = out
        return out


class ServeSession:
    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float, workdir: Path, *, annotate):
        self.cfg, self.traffic, self.seed, self.seconds = cfg, traffic, seed, seconds
        self.workdir = Path(workdir)
        self.annotate = annotate
        self.serve_cfg = cfg["serve"]
        self.ref = reference(cfg)
        self.dtype = getattr(jnp, cfg["dtype"])
        self.cache_dtype = getattr(jnp, cfg["serve"]["cache_dtype"])

    def program_config(self):
        from repro.configs import get

        arch = get(self.cfg["program_config"])
        for key, path in self.ref.PROGRAM_KEYS.items():
            try:
                value = functools.reduce(getattr, path.split("."), arch)
            except AttributeError:
                value = "<absent>"
            if value != self.cfg[key]:
                raise ValueError(
                    f"{self.cfg['program_config']}.{path} = {value!r} but the "
                    f"benchmark runs {key} = {self.cfg[key]!r}"
                )
        return arch

    def setup(self, warm: bool = True) -> None:
        from repro.data.batching import seq2seq_specs
        from repro.models.lm import LM
        from repro.runtime.serve_loop import RingCache, TextRequest, serve_text
        from repro.runtime.summarizer import case_study_chain, serving_chain

        sc = self.serve_cfg
        t0 = time.perf_counter()
        vocab_dir = self.workdir / "vocab_corpus"
        corpus.write_corpus(vocab_dir, int(sc["vocab_corpus_mb"] * 1e6), sc["vocab_shards"], self.seed)
        self.vocab_dir = vocab_dir
        tok = (
            case_study_chain([vocab_dir])
            .workers(sc["workers"], executor=sc["executor"])
            .fit_vocab(vocab_size=sc["row_vocab_size"])
        )
        spec = seq2seq_specs(sc["encoder_len"], 1)[0]
        program = serving_chain([vocab_dir]).tokenize(tok, [spec]).row_program()
        self.row_program = RecordingRowProgram(program, self.annotate)
        t1 = time.perf_counter()

        self.model = LM(self.program_config(), remat=False, dtype=self.dtype)
        key = prng_key(self.seed)
        # the key is an argument, so one compiled program serves every seed
        self.params = jax.block_until_ready(jax.jit(lambda k: self.ref.init_params(k, self.cfg))(key))
        self.serve_text, self.TextRequest, self.RingCache = serve_text, TextRequest, RingCache
        t2 = time.perf_counter()
        self.requests = schedule(self.traffic, self.seconds, self.seed)
        if warm:
            self.warm(self.requests)
        print(f"setup: vocabulary and row program {t1 - t0:.1f} s, weights {t2 - t1:.1f} s, "
              f"warm-up {time.perf_counter() - t2:.1f} s", file=sys.stderr)

    def warm(self, requests) -> None:
        """Make ``requests`` the window's traffic, and warm every prompt
        length it uses through ``serve_text`` itself."""
        self.requests = requests
        program = self.row_program.program
        lengths = set()
        for _, text, _ in requests:
            row = program(text)
            if row is not None:
                lengths.add(int(np.count_nonzero(row[program.output_names[0]][0])))
        warm_new = 2 if max(m for _, _, m in requests) > 1 else 1
        warm = [self.TextRequest(-1 - i, " ".join(["zz"] * n), warm_new) for i, n in enumerate(sorted(lengths))]
        print(f"warm-up: {len(warm)} prompt lengths for {len(requests)} requests", file=sys.stderr)
        q = self.serve_cfg["queue_size"]
        for i in range(0, len(warm), q):
            self._wave(warm[i : i + q], self.RingCache(), program, None)

    def _wave(self, reqs, cache, row_program, stats):
        sc = self.serve_cfg
        return self.serve_text(
            self.model, self.params, row_program, reqs, slots=sc["slots"], max_seq=sc["max_seq"],
            cache=cache, cache_dtype=self.cache_dtype, stats=stats,
        )

    # -- window ------------------------------------------------------------
    def window(self, on_trace=None) -> dict:
        from repro.runtime.serve_loop import ServeStats

        seconds = self.seconds
        reqs = self.requests
        stats, cache = ServeStats(), self.RingCache()
        results: dict = {}
        latency: dict = {}
        late: list = []
        t0 = time.perf_counter()
        trace_at = t0 + seconds - self.traffic["trace_seconds"] if on_trace else None
        trace = None
        i = 0
        while i < len(reqs):
            now = time.perf_counter()
            if now < t0 + reqs[i][0]:
                time.sleep(t0 + reqs[i][0] - now)
                now = time.perf_counter()
                late.append(now - t0 - reqs[i][0])
            if trace_at is not None and trace is None and now >= trace_at:
                trace = on_trace()
                now = time.perf_counter()
            j = i
            while j < len(reqs) and t0 + reqs[j][0] <= now:
                j += 1
            wave = [self.TextRequest(k, reqs[k][1], reqs[k][2]) for k in range(i, j)]
            with self.annotate("serve.wave"):
                out = self._wave(wave, cache, self.row_program, stats)
            for k in range(i, j):
                if k in out:
                    results[k] = out[k]
                    latency[k] = now - t0 - reqs[k][0] + stats.latency_s[k]
            i = j
        end = time.perf_counter()
        if trace:
            trace.end_window()
            trace = trace.stop()
        self.results, self.rejected = results, stats.rejected
        served = [k for k in results if results[k]]
        work = sum(
            self.ref.request_flops(self.cfg, self._prompt_len(k), len(results[k])) for k in served
        )
        lat = np.array([latency[k] for k in served])
        return {
            "window_s": end - t0,
            "attempted": len(reqs),
            "shed": stats.rejected,
            "served": len(served),
            "tokens": int(sum(len(results[k]) for k in served)),
            "latency_s": lat,
            "preprocess_s": stats.preprocess_s,
            "flops": work,
            "generator_late_s": float(max(late)) if late else 0.0,
            "trace": trace,
        }

    def _prompt_len(self, k: int) -> int:
        row = self.row_program.rows.get(self.requests[k][1])
        return int(np.count_nonzero(row[self.row_program.output_names[0]][0])) if row else 0

    def close(self) -> None:
        pass

    # -- check -------------------------------------------------------------
    def check(self, control: bool = False) -> dict:
        sc = self.serve_cfg
        _, vocab = ref_text.token_rows(self.vocab_dir, sc["row_vocab_size"], sc["encoder_len"], 2)
        eos = 2
        wrong_answers = 0
        for k, answer in self.results.items():
            _, _, max_new = self.requests[k]
            whole = len(answer) == max_new or (0 < len(answer) < max_new and answer[-1] == eos)
            wrong_answers += not whole
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 13]))
        answered = [k for k in sorted(self.results) if self.results[k]]
        out = {
            "answers_missing": float(len(self.requests) - len(self.results) - self.rejected),
            "answers_wrong": float(wrong_answers),
        }
        if not answered:
            return {**out, "token_rows_wrong": NONE_READ, "logit_gap": NONE_READ, "logit_gap_mean": NONE_READ}
        longest = max(answered, key=lambda k: len(self.results[k]))
        sample, n_tok = [longest], len(self.results[longest])
        for k in rng.permutation(answered):
            if n_tok >= self.traffic["sample_tokens"]:
                break
            if k != longest:
                sample.append(int(k))
                n_tok += len(self.results[k])
        rows_wrong = 0
        prompts, served = [], []
        for k in sample:
            text = self.requests[k][1]
            want = ref_text.prompt_ids(text, vocab, sc["encoder_len"])
            got = self.row_program.rows.get(text)
            got = () if got is None else tuple(int(t) for t in got[self.row_program.output_names[0]][0] if t)
            rows_wrong += got != want
            prompts.append(want)
            served.append(self.results[k])
        out.update(token_rows_wrong=float(rows_wrong), sampled_tokens=float(n_tok))
        if control:
            gaps, ctrl = self.ref.served_gaps(self.params, prompts, served, self.cfg, control=True)
            out["control_logit_gap"] = float(ctrl.max())
            out["control_logit_gap_mean"] = float(ctrl.mean())
        else:
            gaps = self.ref.served_gaps(self.params, prompts, served, self.cfg)
        out["logit_gap"] = float(gaps.max())
        out["logit_gap_mean"] = float(gaps.mean())
        return out

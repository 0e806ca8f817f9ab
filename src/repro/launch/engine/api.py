"""Engine front-end: SamplingParams, request handles, streaming outputs.

The Engine owns request admission and the step loop; backends own the
device state (dense cache or paged pools) and implement three methods:
``enqueue(handle)``, ``step() -> list[RequestOutput]`` and ``stats()``.
Every token is *emitted the step it is sampled* (prefill included), so
``step()`` doubles as the streaming interface; the final decode step of a
request never pays for caching a token nobody will attend.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional, Sequence

from repro.models.model import Model
from repro.models.transformer import RunCtx


def prefill_bucket(n: int, floor: int, cap: int) -> int:
    """Shared prompt-bucket policy for BOTH backends: the smallest power
    of two >= max(n, floor), clamped to cap. One helper so static and
    paged compile the SAME O(log(max_len / floor)) prefill buckets on any
    trace — the floor (the engine's block size) cuts the sub-block
    buckets the static backend used to compile on short prompts (7 vs 4
    compiles on the bench smoke trace before unification)."""
    return min(max(1 << max(n - 1, 0).bit_length(), floor), cap)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding parameters.

    ``temperature <= 0`` selects greedy (argmax) decoding; otherwise
    logits are temperature-scaled, truncated to the ``top_k`` highest
    and to the top-p nucleus, then sampled from the request's own RNG
    stream.

    Parameters
    ----------
    max_tokens : int
        Retire the request after this many emitted tokens (>= 1).
    temperature : float
        Softmax temperature; ``<= 0`` selects greedy decoding.
    top_k : int
        Keep only the ``top_k`` highest logits (0 disables; ties at
        the threshold survive — the standard caveat).
    top_p : float
        Nucleus sampling in (0, 1]: keep the smallest prefix of
        descending-probability tokens with cumulative mass >= top_p.
    seed : int
        Derives the request's own RNG stream: token t draws from
        ``fold_in(PRNGKey(seed), t)``, so sampled outputs are
        reproducible and independent of admission order, slot
        placement, co-batched traffic, preemption history, replica
        placement and speculative decoding. Requests SHARING a seed
        share the stream (two identical prompts sample identically) —
        pass distinct seeds when you want diversity, e.g. best-of-n
        over one prompt.
    stop_token_ids : tuple of int
        Retire the request on match (the stop token is stripped, never
        emitted), on top of the engine-level ``eos_id``.

    Raises
    ------
    ValueError
        On ``max_tokens < 1``, ``top_p`` outside (0, 1], or negative
        ``top_k``.
    """

    max_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    stop_token_ids: tuple[int, ...] = ()

    def __post_init__(self):
        if self.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 disables)")

    @property
    def greedy(self) -> bool:
        """True when this request decodes greedily (temperature <= 0)."""
        return self.temperature <= 0.0


@dataclasses.dataclass(frozen=True)
class Request:
    """One unit of admission work, as submitted.

    ``Engine.add_request`` accepts either a bare prompt or a Request;
    the Request form is how encoder-decoder workloads attach their
    encoder features. The same object travels unchanged through the
    ``ReplicaSet`` shared queue and ``DisaggregatedEngine`` migration
    packets — validation happens once, at submission.

    Parameters
    ----------
    prompt : sequence of int
        Decoder prompt token ids (>= 1 token; for enc-dec models this
        is the decoder-side prompt, e.g. whisper's task tokens).
    sampling : SamplingParams, optional
        Decoding parameters; defaults to ``SamplingParams()``.
    encoder_features : array or None
        Precomputed encoder-frontend embeddings of shape
        ``(frames, d_model)`` — whisper log-mel conv frames or qwen2-vl
        patch embeds per ``input_specs``. Required for enc-dec configs,
        rejected otherwise (``Engine.check_request``). Submitting the
        SAME array object with several requests shares one cross-KV
        arena row by refcount (e.g. best-of-n over one audio clip).
    """

    prompt: Sequence[int]
    sampling: Optional[SamplingParams] = None
    encoder_features: Any = None


@dataclasses.dataclass
class RequestHandle:
    """Live view of one request; ``token_ids`` grows as the engine steps.

    Attributes
    ----------
    uid : int
        Engine-assigned request id (matches ``RequestOutput.request_id``).
    prompt : list of int
        The prompt token ids as submitted.
    sampling : SamplingParams
        The request's decoding parameters.
    token_ids : list of int
        Tokens emitted so far, in order (stop tokens are stripped).
    finished : bool
        True once the request retired.
    finish_reason : str or None
        ``"length"`` (max_tokens) or ``"stop"`` (eos / stop token).
    num_preemptions : int
        Times this request was LIFO-preempted and later resumed.
    num_draft_proposed, num_draft_accepted : int
        Speculative-decoding counters: draft tokens proposed for /
        accepted into this request (0 unless ``spec_tokens > 0``) —
        the per-request source of truth behind
        ``Engine.stats()["spec"]``.
    t_submit, t_first_token : float or None
        Monotonic-clock stamps at handle creation and at the first
        sampled token; their difference is the request's TTFT,
        aggregated into p50/p95/p99 by ``latency_stats`` (surfaced via
        ``Engine.stats()["latency"]`` and ``ReplicaSet.stats()``).
    t_admitted : float or None
        Monotonic stamp when the request first left the queue for a
        slot (a preempted request keeps it): ``t_admitted - t_submit``
        is its queue wait, ``t_first_token - t_admitted`` its admission
        to first token.
    t_tokens : list of float
        Monotonic stamp per *sampled* token (stripped stop tokens
        included — the stream advanced even though nothing was
        emitted). Mean inter-token gap is the request's TPOT;
        aggregated by ``latency_stats``.
    encoder_features : array or None
        The submitted ``Request.encoder_features``, carried with the
        handle through replica queues and migration packets (the
        cross-KV arena row is recomputed from it on (re-)admission).
    """

    uid: int
    prompt: list[int]
    sampling: SamplingParams
    encoder_features: Any = None
    token_ids: list[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[str] = None      # "length" | "stop"
    num_preemptions: int = 0
    # speculative decoding: drafts proposed for / accepted into this
    # request (the bench's accepted-tokens-per-step source of truth)
    num_draft_proposed: int = 0
    num_draft_accepted: int = 0
    # latency telemetry: stamped at submission / first sampled token /
    # every sampled token (monotonic clock throughout)
    t_submit: float = dataclasses.field(default_factory=time.monotonic)
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_tokens: list[float] = dataclasses.field(default_factory=list)
    # internal: RNG stream position (== tokens sampled; differs from
    # len(token_ids) only after a stripped stop token)
    _n_sampled: int = 0

    @property
    def out(self) -> list[int]:
        """Legacy PR-1 ``Scheduler`` alias for ``token_ids``."""
        return self.token_ids

    @property
    def done(self) -> bool:
        """Legacy PR-1 ``Scheduler`` alias for ``finished``."""
        return self.finished


@dataclasses.dataclass(frozen=True)
class RequestOutput:
    """One streaming increment: tokens a request gained this step.

    Attributes
    ----------
    request_id : int
        The owning request's ``RequestHandle.uid``.
    new_tokens : tuple of int
        Tokens emitted this step — usually one; empty on a stripped
        stop token; several under speculative decoding.
    num_tokens : int
        Total tokens emitted for the request so far.
    finished : bool
        True when this increment retires the request.
    finish_reason : str or None
        ``"length"`` or ``"stop"`` when ``finished``, else None.
    """

    request_id: int
    new_tokens: tuple[int, ...]
    num_tokens: int                          # total emitted so far
    finished: bool
    finish_reason: Optional[str] = None


def register_sample(req: RequestHandle, tok: int, eos_id: int,
                    on_finish) -> RequestOutput:
    """Shared token-acceptance state machine for all backends: advance
    the request's RNG stream, strip stop tokens, retire on stop or
    max_tokens, and emit the streaming increment. ``on_finish()`` runs
    backend cleanup (free blocks / park the lane) after the handle's
    finished/finish_reason flags are set — keeping both backends on
    byte-identical emission semantics."""
    now = time.monotonic()
    req._n_sampled += 1
    req.t_tokens.append(now)
    if req._n_sampled == 1:
        req.t_first_token = now
    stop = (eos_id >= 0 and tok == eos_id) \
        or tok in req.sampling.stop_token_ids
    if not stop:
        req.token_ids.append(tok)
        if len(req.token_ids) < req.sampling.max_tokens:
            return RequestOutput(req.uid, (tok,), len(req.token_ids),
                                 False)
    reason = "stop" if stop else "length"
    req.finished = True
    req.finish_reason = reason
    on_finish()
    return RequestOutput(req.uid, () if stop else (tok,),
                         len(req.token_ids), True, reason)


def _pctl(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample (no
    interpolation — p99 of 3 samples is the max, not an extrapolation)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return float(sorted_vals[i])


def latency_stats(handles) -> dict:
    """Aggregate per-request latency stamps into TTFT/TPOT percentiles.

    TTFT is ``t_first_token - t_submit`` per request; TPOT is the mean
    inter-token gap ``(t_tokens[-1] - t_tokens[0]) / (n - 1)`` over
    requests with at least two sampled tokens. Both are summarized with
    nearest-rank percentiles. This is the one aggregation behind
    ``Engine.stats()["latency"]``, ``ReplicaSet.stats()`` and the bench
    ``open_loop`` section, so every surface reports the same numbers.

    Parameters
    ----------
    handles : iterable of RequestHandle
        Finished and/or in-flight handles; requests with no sampled
        token yet contribute to neither distribution.

    Returns
    -------
    dict
        ``{"ttft": {count, mean_s, p50_s, p95_s, p99_s},
        "tpot": {...}}`` — zeros when a distribution is empty.
    """
    ttft = sorted(h.t_first_token - h.t_submit for h in handles
                  if h.t_first_token is not None)
    tpot = sorted((h.t_tokens[-1] - h.t_tokens[0]) / (len(h.t_tokens) - 1)
                  for h in handles if len(h.t_tokens) >= 2)

    def summarize(vals):
        return {"count": len(vals),
                "mean_s": float(sum(vals) / len(vals)) if vals else 0.0,
                "p50_s": _pctl(vals, 0.50),
                "p95_s": _pctl(vals, 0.95),
                "p99_s": _pctl(vals, 0.99)}

    return {"ttft": summarize(ttft), "tpot": summarize(tpot)}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine/backends configuration (immutable; shared by replicas).

    Parameters
    ----------
    backend : {"paged", "static"}
        ``"paged"`` — continuous batching over the block-paged KV pool
        (becomes the speculative backend when ``spec_tokens > 0``);
        ``"static"`` — the lockstep right-padded baseline.
    num_slots : int
        Decode batch width (concurrent sequences on device).
    block_size, num_blocks : int
        Paged pool geometry: tokens per cache block and pool size
        (block 0 is the reserved null block).
    max_len : int
        Per-sequence position cap (prompt + output).
    eos_id : int
        Engine-level stop token; -1 retires on length only.
    watermark_blocks : int
        Paged admission headroom: keep this many blocks free for
        in-flight growth while admitting new sequences.
    bucketed_prefill : bool
        Right-pad prompts to power-of-two buckets when the model
        supports ragged prefill (O(log max_len) prefill compiles).
    max_prefill_batch : int
        Cap on requests prefilled in one batched admission call;
        <= 0 lifts the cap to the slot count.
    prefix_cache : bool
        Copy-on-write prefix caching on the paged backend: admissions
        match the longest block-aligned cached prefix against a
        host-side trie, share those physical blocks by refcount, and
        prefill only the non-shared suffix; retirement parks
        unreferenced indexed blocks in an LRU reclaimed before the
        allocator reports exhaustion. Active only when the model's
        whole state lives in the shared pool
        (``ServingCaps.prefix_cache``); outputs are token-identical
        with it on or off.
    mesh : jax.sharding.Mesh or None
        Shard params (2-D FSDP x TP), the KV pool (head-sharded over
        ``tp_axis``) and the compiled steps over this mesh. Host-side
        scheduling is unchanged; tokens are mesh-independent.
    tp_axis : str
        Tensor-parallel mesh axis name.
    spec_tokens : int
        Speculative decoding: draft tokens proposed per request per
        step (K); the verify step scores K+1 positions in one pass.
        0 disables (see launch/engine/speculative.py).
    drafter : {"ngram", "draft_model"}
        Speculative proposal source: zero-parameter prompt lookup, or
        a small draft model passed via ``draft_model``/``draft_params``.
    ngram_max : int
        Longest history suffix the ngram drafter keys on.
    draft_model, draft_params
        The draft ``Model`` (attention-only, same vocab) and params for
        ``drafter="draft_model"``.
    kv_dtype : {"bf16", "int8", "fp8"}
        Storage precision of the paged K/V block pool. ``"bf16"`` keeps
        the historical full-precision pool (bit-identical outputs).
        ``"int8"``/``"fp8"`` (float8_e4m3) store quantized payloads
        with per-(token, kv-head) f32 scale leaves alongside in the
        pool tree; dequant is fused into the decode/verify kernels, so
        no full-precision copy of the pool is ever materialized.
        Requires ``ServingCaps.quantized_kv`` and the paged backend.
    overlap : bool
        Async host/device overlap on the paged backend: ``step()``
        dispatches the NEXT decode (feeding the in-flight sampled
        tokens device-to-device) *before* blocking on the previous
        step's token fetch, so host-side scheduling/admission work
        hides under device compute. Outputs are bit-identical with it
        on or off (the RNG-stream contract — the overlapped dispatch
        changes when work runs, never what is computed). Requires the
        paged backend and ``spec_tokens == 0``.
    """

    backend: str = "paged"       # "paged" | "static"
    num_slots: int = 8           # decode batch width
    block_size: int = 16         # paged: tokens per cache block
    num_blocks: int = 512        # paged: pool size (block 0 reserved)
    max_len: int = 256           # per-sequence position cap
    eos_id: int = -1             # -1: length-based retirement only
    watermark_blocks: int = 0    # paged: admission headroom (see alloc)
    bucketed_prefill: bool = True  # pow-2 prompt buckets (when exact)
    # Batched prefill admission: each paged admission drains up to this
    # many queued requests sharing one prefill bucket and prefills them
    # in a single right-padded batch call (one jit trace per (bucket,
    # batch-bucket) pair); the static backend bounds its lockstep batch
    # width with it. <= 0 (default) lifts the cap to the slot count.
    max_prefill_batch: int = 0
    # Copy-on-write prefix caching (paged backend): share block-aligned
    # cached prompt prefixes across requests via refcounts, prefill only
    # the non-shared suffix, keep unreferenced indexed blocks in an LRU
    # reclaimed before exhaustion. Silently inactive for models with
    # per-slot decode state (rings/SSM) or cross-attention — see
    # ServingCaps.prefix_cache.
    prefix_cache: bool = True
    # Mesh-sharded serving: when a jax.sharding.Mesh is given, the
    # backend shards params (2-D FSDP x TP rules of launch/sharding.py),
    # the KV block pools (head-sharded over ``tp_axis`` — each device
    # owns its kv-head shard of every block; block tables and lengths
    # stay replicated host state) and per-slot caches, and compiles the
    # prefill/decode steps against NamedSharding so device placement is
    # stable across steps. Host-side scheduling is unchanged.
    mesh: Any = None             # jax.sharding.Mesh | None
    tp_axis: str = "model"       # tensor-parallel mesh axis name
    # Speculative decoding (paged backend only): each scheduled request
    # proposes up to ``spec_tokens`` draft tokens per step and the
    # target model verifies the whole window in ONE batched pass
    # (engine/speculative.py). 0 disables. ``drafter`` picks the
    # proposal source: "ngram" (zero-extra-params prompt lookup) or
    # "draft_model" (a small model passed via draft_model/draft_params,
    # sharing the target's tokenizer/config machinery).
    spec_tokens: int = 0
    drafter: str = "ngram"       # "ngram" | "draft_model"
    ngram_max: int = 3           # longest suffix the ngram drafter keys on
    draft_model: Any = None      # Model (drafter="draft_model")
    draft_params: Any = None     # its params
    # Paged KV pool storage precision: "bf16" (full precision,
    # bit-identical), "int8" or "fp8" (float8_e4m3 payloads +
    # per-(token, kv-head) scale leaves, dequant fused into the kernels).
    kv_dtype: str = "bf16"       # "bf16" | "int8" | "fp8"
    # Async host/device overlap (paged backend): dispatch decode N+1
    # before fetching decode N's sampled tokens (double-buffered token
    # fetch; admission prefills are ordered after the in-flight decode
    # by the functional pool data dependency). Bit-identical outputs.
    overlap: bool = False


class Engine:
    """Single serving front-end over pluggable execution backends.

    The Engine owns request validation and the step loop; the backend
    owns device state and scheduling (admission, growth, preemption,
    retirement). Three workload classes share the one stack: dense
    decoder-only text LMs, MoE LMs (expert-sharded decode under a
    mesh), and encoder-decoder models whose requests carry encoder
    features (``Request.encoder_features`` -> per-slot cross-KV arena).

    Parameters
    ----------
    model : Model
        The target model; configs without a paged decode path
        (``ServingCaps.paged_decode`` — e.g. qwen2-vl's mrope/visual
        prefix frontend) raise NotImplementedError.
    params
        Its parameter tree (placed onto ``cfg.mesh`` when sharded).
    cfg : EngineConfig, optional
        Backend selection and geometry; defaults to ``EngineConfig()``.
    ctx : RunCtx, optional
        Kernel/sharding context; defaults to ``kernel_mode="auto"``:
        the Pallas kernels on a TPU, the jnp references elsewhere.

    Attributes
    ----------
    backend : PagedBackend | SpecDecodeBackend | StaticBackend
        The execution backend selected by ``cfg``.
    finished : list of RequestHandle
        Handles retired so far, in completion order.

    Notes
    -----
    Every token is *emitted the step it is sampled* (prefill included),
    so ``step()`` doubles as the streaming interface. Outputs obey the
    RNG-stream contract (see ``SamplingParams.seed`` and
    docs/serving.md): they do not depend on admission order, slot
    placement, co-batched traffic, preemption, sharding, replica
    placement, or speculative decoding.

    Invariants the tests rely on: the FCFS queue head is never
    overtaken (admission drains a queue *prefix*); zero block leaks —
    every pool block returns to the allocator on retirement,
    preemption, and speculative rejected-tail rewind (double-frees
    raise); both backends compile the same power-of-two prefill bucket
    set (``prefill_bucket``), keeping the jit cache at
    O(buckets x batch-buckets).

    Examples
    --------
    >>> engine = Engine(model, params, EngineConfig(backend="paged"))
    >>> handle = engine.add_request(prompt, SamplingParams(max_tokens=8))
    >>> while engine.has_work:
    ...     for out in engine.step():
    ...         print(out.request_id, out.new_tokens)
    """

    def __init__(self, model: Model, params, cfg: EngineConfig = None,
                 ctx: Optional[RunCtx] = None):
        from repro.launch.engine.scheduler import PagedBackend
        from repro.launch.engine.static import StaticBackend

        self.cfg = cfg or EngineConfig()
        self.model = model
        mc = model.cfg
        self.caps = model.serving_caps()
        if not self.caps.paged_decode:
            raise NotImplementedError(
                f"no paged decode path for config {mc.family}/{mc.name}: "
                "mrope / visual-prefix frontends (qwen2-vl) and "
                "decoder-only absolute-position embeddings are not "
                "served (ServingCaps.paged_decode)")
        if self.caps.cross_attn and self.cfg.backend == "static":
            raise ValueError(
                "encoder-decoder serving needs the paged backend "
                "(the cross-KV arena lives in the paged pool); use "
                "backend='paged'")
        if self.caps.cross_attn and self.cfg.spec_tokens > 0:
            raise ValueError(
                "speculative decoding is decoder-only: the verify pass "
                "has no cross-attention path; set spec_tokens=0 for "
                f"{mc.family}/{mc.name}")
        from repro.models.paged_kv import KV_DTYPES
        if self.cfg.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {self.cfg.kv_dtype!r}; expected one "
                f"of {KV_DTYPES}")
        if self.cfg.kv_dtype != "bf16":
            if self.cfg.backend == "static":
                raise ValueError(
                    "quantized KV (kv_dtype="
                    f"{self.cfg.kv_dtype!r}) requires the paged backend "
                    "— the static baseline keeps dense full-precision "
                    "caches; use backend='paged'")
            if not self.caps.quantized_kv:
                raise ValueError(
                    f"config {mc.family}/{mc.name} does not support a "
                    f"quantized paged KV pool (kv_dtype="
                    f"{self.cfg.kv_dtype!r}): ServingCaps.quantized_kv "
                    "is False — encoder-decoder cross-KV arenas and "
                    "non-paged frontends stay bf16")
        ctx = ctx or RunCtx(kernel_mode="auto")
        if self.cfg.mesh is not None and ctx.shard is None:
            from repro.launch.sharding import make_shard_ctx
            from repro.models.paged_kv import head_shard_ok

            shard = make_shard_ctx(self.cfg.mesh,
                                   tp_axis=self.cfg.tp_axis)
            ctx = dataclasses.replace(
                ctx, shard=shard,
                decode_head_shard=head_shard_ok(mc, shard.tp_size))
        # Expert-sharded decode: shard_map the MoE FFN over the model
        # axis when the widths divide (decode/verify run at num_slots
        # width; the scheduler drops back to GSPMD for pow-2 prefill
        # buckets, which need not divide dp — see PagedBackend).
        if (self.caps.moe and ctx.shard is not None
                and self.cfg.backend == "paged"
                and mc.n_experts % ctx.shard.tp_size == 0
                and self.cfg.num_slots % ctx.shard.dp_size == 0):
            ctx = dataclasses.replace(ctx, moe_sharded=True)
        if self.cfg.overlap:
            if self.cfg.backend != "paged":
                raise ValueError(
                    "overlap=True requires the paged backend — the "
                    "static baseline fetches lockstep; use "
                    "backend='paged'")
            if self.cfg.spec_tokens > 0:
                raise ValueError(
                    "overlap=True is incompatible with speculative "
                    "decoding: the verify step consumes the sampled "
                    "tokens on the host before the next dispatch; set "
                    "spec_tokens=0")
        if self.cfg.backend == "paged":
            if self.cfg.spec_tokens > 0:
                from repro.launch.engine.speculative import SpecDecodeBackend
                self.backend = SpecDecodeBackend(model, params, self.cfg,
                                                 ctx)
            else:
                self.backend = PagedBackend(model, params, self.cfg, ctx)
        elif self.cfg.backend == "static":
            if self.cfg.spec_tokens > 0:
                raise ValueError(
                    "speculative decoding requires the paged backend")
            self.backend = StaticBackend(model, params, self.cfg, ctx)
        else:
            raise ValueError(f"unknown backend {self.cfg.backend!r}")
        self._uid = 0

    # -- request lifecycle ----------------------------------------------

    def check_request(self, prompt: Sequence[int],
                      sampling: SamplingParams,
                      encoder_features=None):
        """Raise ValueError when this engine could never serve the
        request (empty prompt, position cap, backend capacity bound,
        encoder features absent/present against the config's declared
        ``ServingCaps.cross_attn``). Shared by ``add_request`` and the
        ReplicaSet front-end, which validates once against a
        representative replica before the request enters the shared
        queue."""
        mc = self.model.cfg
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if len(prompt) + sampling.max_tokens > self.cfg.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens "
                f"({sampling.max_tokens}) exceeds max_len "
                f"{self.cfg.max_len}")
        if encoder_features is not None and not self.caps.cross_attn:
            raise ValueError(
                f"encoder features on a non-encoder-decoder config: "
                f"{mc.family}/{mc.name} has no cross-attention "
                f"(enc_dec=False) — drop Request.encoder_features, or "
                f"serve an enc-dec config (e.g. whisper)")
        if self.caps.cross_attn:
            if encoder_features is None:
                raise ValueError(
                    f"encoder-decoder config {mc.family}/{mc.name} "
                    f"needs Request.encoder_features (a "
                    f"(frames, {mc.d_model}) array — whisper mel-conv "
                    f"frames per input_specs); bare prompts are "
                    f"decoder-only")
            shape = getattr(encoder_features, "shape", None)
            if shape is None or len(shape) != 2 or shape[1] != mc.d_model:
                raise ValueError(
                    f"encoder_features must be a (frames, d_model="
                    f"{mc.d_model}) array, got shape {shape}")
            if not 1 <= shape[0] <= mc.encoder_len:
                raise ValueError(
                    f"encoder_features frames ({shape[0]}) outside "
                    f"[1, encoder_len={mc.encoder_len}] for "
                    f"{mc.family}/{mc.name}")
        check = getattr(self.backend, "check_request", None)
        if check is not None:            # paged: worst-case pool bound
            check(len(prompt), sampling)

    def add_request(self, prompt,
                    sampling: Optional[SamplingParams] = None,
                    encoder_features=None) -> RequestHandle:
        """Validate and enqueue one request; returns its live handle.
        ``prompt`` is a token-id sequence or a ``Request`` (the latter
        carries sampling and encoder features itself)."""
        if isinstance(prompt, Request):
            if sampling is not None or encoder_features is not None:
                raise ValueError("pass sampling/encoder_features inside "
                                 "the Request, not alongside it")
            sampling = prompt.sampling
            encoder_features = prompt.encoder_features
            prompt = prompt.prompt
        sampling = sampling or SamplingParams()
        prompt = list(prompt)
        self.check_request(prompt, sampling, encoder_features)
        handle = RequestHandle(self._uid, prompt, sampling,
                               encoder_features=encoder_features)
        self._uid += 1
        self.backend.enqueue(handle)
        return handle

    def step(self) -> list[RequestOutput]:
        """Admissions + one device step; streams per-request increments."""
        return self.backend.step()

    @property
    def has_work(self) -> bool:
        """True while any request is waiting or active."""
        return self.backend.has_work

    @property
    def finished(self) -> list[RequestHandle]:
        """Handles retired so far, in completion order."""
        return self.backend.finished

    def stats(self) -> dict:
        """Backend telemetry: occupancy, cache utilization, preemption
        and prefill-compile counters — plus a ``"spec"`` section
        (aggregate and per-request draft counters) when speculative
        decoding is on, and a ``"latency"`` section (TTFT/TPOT
        p50/p95/p99 over finished and in-flight requests, see
        ``latency_stats``). docs/benchmarks.md documents the derived
        bench fields."""
        st = self.backend.stats()
        live = getattr(self.backend, "live_handles", None)
        handles = list(self.backend.finished)
        if live is not None:
            handles += live()
        st["latency"] = latency_stats(handles)
        return st

    @property
    def made_progress(self) -> bool:
        """True when the last ``step()`` admitted, decoded or preempted
        (the stall detector in ``drive`` keys on it)."""
        return self.backend.made_progress

    # -- convenience drivers --------------------------------------------

    def drain(self, max_steps: int = 100_000) -> list[RequestOutput]:
        """Step until idle; returns the concatenated output stream."""
        return drive(self, max_steps,
                     "engine stalled: waiting requests cannot be admitted")

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling=None, max_steps: int = 100_000,
                 encoder_features=None) -> list[list[int]]:
        """Submit ``prompts`` and drive to completion; returns token ids
        per prompt in submission order. ``sampling`` is one
        SamplingParams for all or a per-prompt sequence;
        ``encoder_features`` a per-prompt sequence of feature arrays
        for enc-dec models (entries may repeat to share arena rows)."""
        return run_generate(self, prompts, sampling, max_steps,
                            encoder_features=encoder_features)


def drive(engine, max_steps: int, stall_msg: str) -> list[RequestOutput]:
    """Drive-to-completion loop shared by every Engine-shaped front-end
    (Engine, ReplicaSet): step until idle, guard the step budget, raise
    on a stall (a step that neither emitted nor progressed)."""
    stream: list[RequestOutput] = []
    steps = 0
    while engine.has_work:
        outs = engine.step()
        stream.extend(outs)
        steps += 1
        if steps > max_steps:
            raise RuntimeError("step budget exceeded")
        if not outs and not engine.made_progress:
            raise RuntimeError(stall_msg)
    return stream


def run_generate(engine, prompts, sampling, max_steps,
                 encoder_features=None) -> list[list[int]]:
    """Shared ``generate`` driver: broadcast/validate sampling params,
    submit everything, drain, collect per-prompt tokens in order."""
    if sampling is None or isinstance(sampling, SamplingParams):
        sampling = [sampling or SamplingParams()] * len(prompts)
    if len(sampling) != len(prompts):
        raise ValueError(f"{len(sampling)} sampling params for "
                         f"{len(prompts)} prompts")
    if encoder_features is None:
        encoder_features = [None] * len(prompts)
    if len(encoder_features) != len(prompts):
        raise ValueError(f"{len(encoder_features)} encoder features for "
                         f"{len(prompts)} prompts")
    handles = [engine.add_request(p, s, encoder_features=f)
               for p, s, f in zip(prompts, sampling, encoder_features)]
    engine.drain(max_steps=max_steps)
    return [list(h.token_ids) for h in handles]

"""Model-agnostic batched serving: one scheduler loop, two backends.

`launch.scheduler.FleetScheduler` owns the queue, batch bucketing, slot
retirement, backfill and replica placement (one backend is a fleet of
one); this module plugs in the model math:

* `LMBackend` / `Server` — the production prefill/decode jits with working
  continuous batching.  A sequence retires the moment it emits ``eos_id``
  (or exhausts its ``max_new`` budget) and its slot is backfilled from the
  queue in the same run: the newcomer is prefilled left-padded to the
  current context length and its cache rows are merged into the live batch
  (the KV/state cache is donated and updated in place).  A uniform batch
  with no EOS spends exactly ``max_new - 1`` decode steps — the prefill
  emits each slot's first token, so there is no trailing wasted decode.
  Admission prompt lengths are bucketed (``len_bucket``) so first-wave
  prefill compile shapes stay bounded; on attention archs a backfill
  prefill right-pads the context to the same bucket ladder and reads its
  logits at the true position, so backfill shapes are bounded too (one
  executable per bucket, not one per retirement step).  Recurrent archs
  (rwkv/mamba) keep the exact-length backfill prefill — their state folds
  in every processed token — see the ROADMAP serving follow-ups.

* `CNNBackend` / `CNNServer` — CNN inference traffic through
  `SparseNet.apply`: requests carry images, batches pad/bucket on image
  shape, every request finishes in one wave, and freed slots are refilled
  from the queue so the compiled batch shape is reused wave after wave; a
  partial final wave shrinks to its occupied slots (pow2 ladder) instead
  of computing zero images.  The backend is one-shot with the
  dispatch/collect split, so the scheduler runs it one wave ahead.  A jit
  cache keyed on (net, density, impl, batch bucket) — see
  `models.graph.BatchedApply` — keeps recompiles off the hot path;
  ``impl`` defaults to ``auto`` (the halo-layout Pallas conv kernels on
  TPU, the structural jnp path elsewhere).

Both run end-to-end on CPU with reduced configs; the LM jits are the same
step functions the decode_32k / long_500k dry-run cells lower on the
production mesh.

Multi-device serving: ``--replicas N`` serves a `ReplicaGroup` — N
data-parallel CNN backend instances with `jax.device_put`-placed weight
copies — with per-replica waves, least-loaded placement and work stealing.
``--shard-fc`` additionally cout-shards the FC heads' strips over each
replica's ``model`` devices (`models.graph.shard_sparse`).  On CPU, force
a device mesh with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

LM requests carry per-request sampling params (``temperature`` /
``top_k``); temperature 0 is greedy argmax, bit-identical to the
pre-sampling decode path.

Fault tolerance: both backends validate requests at admission
(`validate_request` — malformed images / prompts become structured
`RequestOutcome` refusals, never mid-wave shape errors), `CNNBackend`
guards its outputs (`check_emission` — non-finite logits quarantine the
producing replica, one replica included), and `CNNServer` accepts a
``fault_plan`` (`launch.faults.FaultPlan`) that wraps every replica in a
`ChaosBackend` for deterministic chaos runs, plus ``max_queue`` /
``deadline_waves`` / ``max_attempts`` budgets forwarded to the scheduler.
Per-request outcomes of the last serve land on ``srv.outcomes`` (and each
request's ``.outcome``).

Usage (CPU examples):
  python -m repro.launch.serve --arch rwkv6-3b --requests 16 --tokens 32
  python -m repro.launch.serve --cnn vscnn-vgg16 --requests 16 --batch 8
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python -m repro.launch.serve --cnn vscnn-vgg16 --replicas 4 --shard-fc
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import spans
from repro.launch.faults import ChaosBackend, FaultPlan
from repro.launch.mesh import make_local_mesh
from repro.launch.scheduler import FleetScheduler
from repro.models import transformer as tfm
from repro.models.layers import init_params
from repro.parallel import sharding as shd
from repro.utils.compile_cache import enable_compile_cache

__all__ = [
    "Request", "ImageRequest", "LMBackend", "CNNBackend", "ReplicaGroup",
    "Server", "CNNServer", "random_prompt_lengths", "main",
]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass
class Request:
    """One LM generation request.

    ``temperature``/``top_k`` select per-request sampling for every token
    this request emits: 0 temperature (the default) is greedy argmax,
    bit-identical to a request that never set the fields; ``top_k > 0``
    restricts sampling to the k highest logits.  Requests with different
    sampling params share a batch — the sampler is per-slot.
    """

    rid: int
    prompt: np.ndarray           # (L,) int32
    max_new: int
    temperature: float = 0.0
    top_k: int = 0
    out: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ImageRequest:
    """One CNN inference request."""

    rid: int
    image: np.ndarray            # (H, W, C) float
    max_new: int = 1             # one-shot: a single emission finishes it
    out: list = dataclasses.field(default_factory=list)  # [predicted class]
    logits: np.ndarray | None = None


# --------------------------------------------------------------------------
# LM backend: prefill/decode waves with EOS retirement + cache-merge
# backfill
# --------------------------------------------------------------------------

def _sample_tokens(logits, temp, top_k, keys):
    """Per-slot temperature/top-k sampling over (B, V) logits.

    Slots with ``temp == 0`` take the plain ``jnp.argmax`` branch of the
    final select — the greedy operand is computed from the raw logits, so
    a zero-temperature slot reproduces the greedy path bit-exactly even
    when its batch neighbors sample.  ``top_k == 0`` means no truncation.
    Ranking uses a stable double-argsort, so ``top_k=1`` keeps exactly the
    argmax candidate (first max on ties, like argmax itself).
    """
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    order = jnp.argsort(-logits, axis=-1)
    rank = jnp.argsort(order, axis=-1)          # 0 = largest logit
    k = jnp.where(top_k > 0, top_k, logits.shape[-1])[:, None]
    masked = jnp.where(rank < k, logits, -jnp.inf)
    scaled = masked / jnp.maximum(temp, 1e-30)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temp > 0.0, sampled.astype(jnp.int32), greedy)


def _positional_caches(cfg) -> bool:
    """True when every cached layer state is plain positional attention K/V.

    Recurrent mixers (rwkv/mamba and their channel-mix halves) fold every
    processed token into their state, so a backfill prefill right-padded
    past the true context would corrupt it.  Sliding-window attention is
    excluded too: its K/V cache is *circular* (slot = pos % window), so the
    right-pad junk at positions [cur, curb) would wrap onto slots holding
    real in-window history and be attended as it.  Only plain full-context
    attention caches (slot == position; future slots masked by kpos >= 0,
    then overwritten) survive the right-pad, and they gate the bucketed
    backfill below.
    """
    return all(
        sp.mixer in ("attn", "none") and sp.window is None
        and sp.ffn in ("mlp", "moe", "none")
        for seg in cfg.segments for sp in seg.layers
    )


class LMBackend:
    """Continuous-batching backend over the transformer prefill/decode jits.

    Backfill prefills the newcomer at the full batch width (idle lanes
    zeroed) and merges only its cache rows: the wasted lanes buy two things
    — the prefill compile shape family stays the same as admission's, and a
    backfilled request computes bit-identically to the same request served
    alone at that context length (regression-tested).

    For attention archs the backfill context length is additionally
    *bucketed*: the newcomer's tokens are right-padded from the true
    context length ``cur`` up to the ``len_bucket`` ladder and the first
    token is read at position ``cur - 1`` (`tfm.prefill(logit_pos=...)`),
    so retirements at distinct steps stop compiling a fresh prefill shape
    each — one executable per bucket instead of one per context length.
    The pad rows' K/V junk is causally masked and then overwritten by the
    following decode steps before any query attends it.  Recurrent archs
    (rwkv/mamba) keep the exact-length prefill: their state folds in every
    processed token, pad included (see ROADMAP serving follow-ups).
    """

    def __init__(self, cfg, params, mesh, *, capacity: int,
                 eos_id: int | None = None, len_bucket: int = 16,
                 sample_seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.capacity = capacity
        self.eos_id = eos_id
        self.len_bucket = max(1, len_bucket)
        self.backfill_bucket = (self.len_bucket if _positional_caches(cfg)
                                else 1)
        self.sample_seed = sample_seed
        self._bkey = None
        self._sample = jax.jit(_sample_tokens)
        self._prefill = jax.jit(
            lambda p, b: tfm.prefill(p, b, cfg, capacity=capacity))
        # backfill prefill: logits at a chosen (traced) position, so the
        # compile key is the bucketed token shape only
        self._prefill_at = jax.jit(
            lambda p, b, pos: tfm.prefill(p, b, cfg, capacity=capacity,
                                          logit_pos=pos))
        self._decode = jax.jit(
            lambda p, c, t, pos: tfm.decode_step(p, c, t, pos, cfg),
            donate_argnums=(1,))
        # scatter one prefilled request's cache rows into the live batch;
        # cache leaves are (repeat, batch, ...) so batch is axis 1
        self._merge = jax.jit(
            lambda caches, new, j: jax.tree.map(
                lambda c, n: c.at[:, j].set(n[:, j]), caches, new),
            donate_argnums=(0,))

    # -- per-slot sampling --------------------------------------------------

    @staticmethod
    def _greedy_lane() -> list:
        return [0.0, 0, -1, 0]           # temperature, top_k, rid, count

    def _base_key(self):
        if self._bkey is None:
            self._bkey = jax.random.PRNGKey(self.sample_seed)
        return self._bkey

    def _emit_tokens(self, state, logits, js):
        """Next token for each slot index in ``js``; ``logits[i]`` is slot
        ``js[i]``'s row.  All-greedy batches keep the legacy plain-argmax
        path (bit-identical, no sampler dispatch); otherwise each sampling
        slot draws with a key folded from (seed, rid, emission count), so
        a request's stream is reproducible wherever its slot lands."""
        sel = [state["samp"][j] for j in js]
        if not any(s[0] > 0 for s in sel):
            return jnp.argmax(logits, -1).astype(jnp.int32)
        temps = jnp.asarray([s[0] for s in sel], jnp.float32)
        topks = jnp.asarray([s[1] for s in sel], jnp.int32)
        base = self._base_key()
        keys = jnp.stack([jax.random.fold_in(
            jax.random.fold_in(base, s[2] & 0x7FFFFFFF), s[3])
            for s in sel])
        toks = self._sample(logits, temps, topks, keys)
        for s in sel:
            s[3] += 1
        return toks

    # -- scheduler protocol -------------------------------------------------

    def validate_request(self, req: Request) -> str | None:
        """Admission-time validation: a reason string refuses the request
        (structured `RequestOutcome`) before it can poison a batch."""
        p = req.prompt
        if not isinstance(p, np.ndarray):
            return f"not_an_array:{type(p).__name__}"
        if p.ndim != 1:
            return f"bad_rank:{p.ndim}"
        if not np.issubdtype(p.dtype, np.integer):
            return f"bad_dtype:{p.dtype}"
        if len(p) == 0:
            return "empty_prompt"
        if req.max_new < 1:
            return f"bad_max_new:{req.max_new}"
        padded = _round_up(len(p), self.len_bucket)
        if padded >= self.capacity:
            return f"prompt_too_long:{padded}>={self.capacity}"
        return None

    def reset(self, req: Request) -> None:
        """Clear partial progress before a fault-displaced re-serve.  The
        regenerated stream is bit-identical: sampling keys fold (seed, rid,
        emission count) and the count restarts at 0 with the request."""
        req.out.clear()

    def bucket_key(self, req: Request):
        return _round_up(max(len(req.prompt), 1), self.len_bucket)

    def sort_key(self, req: Request):
        # longest prompts first: every later backfill then fits the
        # already-grown context (can_backfill below)
        return -len(req.prompt)

    def context(self):
        return shd.use_mesh(self.mesh, shd.SERVE_RULES)

    def start(self, requests: list[Request], width: int):
        lens = [len(r.prompt) for r in requests]
        max_len = _round_up(max(max(lens), 1), self.len_bucket)
        if max_len >= self.capacity:
            raise ValueError(
                f"padded prompt length {max_len} >= capacity {self.capacity}")
        toks = np.zeros((width, max_len), np.int32)
        for i, r in enumerate(requests):  # left-pad
            toks[i, max_len - len(r.prompt):] = r.prompt
        logits, caches = self._prefill(
            self.params, {"tokens": jnp.asarray(toks)})
        samp = [[r.temperature, r.top_k, r.rid, 0] for r in requests]
        samp += [self._greedy_lane() for _ in range(width - len(requests))]
        state = {"caches": caches, "nxt": None, "len": max_len, "i": 0,
                 "samp": samp}
        nxt = self._emit_tokens(state, logits, range(width))[:, None]
        state["nxt"] = nxt
        first = np.asarray(nxt[:, 0])
        emis = [int(first[j]) if j < len(requests) else None
                for j in range(width)]
        return state, emis

    def step(self, state, slots):
        logits, caches = self._decode(
            self.params, state["caches"], state["nxt"],
            jnp.int32(state["len"] + state["i"]))
        for j, s in enumerate(slots):
            if s is None:                # retired lane: back to greedy
                state["samp"][j] = self._greedy_lane()
        nxt = self._emit_tokens(state, logits, range(len(slots)))[:, None]
        state.update(caches=caches, nxt=nxt, i=state["i"] + 1)
        toks = np.asarray(nxt[:, 0])
        return state, [int(toks[j]) for j in range(len(slots))]

    def can_backfill(self, state, req: Request) -> bool:
        cur = state["len"] + state["i"]
        return (len(req.prompt) <= cur
                and cur + req.max_new <= self.capacity)

    def backfill(self, state, slot: int, req: Request):
        cur = state["len"] + state["i"]
        width = int(state["nxt"].shape[0])
        # right-pad the context to the bucket ladder: positions [0, cur)
        # are exactly the exact-length prefill's, logits are read at
        # cur - 1, and the junk K/V rows beyond cur are masked/overwritten
        curb = min(_round_up(cur, self.backfill_bucket), self.capacity)
        toks = np.zeros((width, curb), np.int32)
        toks[slot, cur - len(req.prompt):cur] = req.prompt
        logits, caches1 = self._prefill_at(
            self.params, {"tokens": jnp.asarray(toks)}, jnp.int32(cur - 1))
        state["samp"][slot] = [req.temperature, req.top_k, req.rid, 0]
        tok = int(self._emit_tokens(state, logits[slot][None], [slot])[0])
        state["caches"] = self._merge(state["caches"], caches1, slot)
        state["nxt"] = state["nxt"].at[slot, 0].set(tok)
        return state, tok

    def append(self, req: Request, tok: int) -> bool:
        req.out.append(tok)
        if self.eos_id is not None and tok == self.eos_id:
            return True
        return len(req.out) >= req.max_new

    def finish(self, state) -> dict:
        jax.block_until_ready(state["nxt"])
        return {}


class Server:
    """Batched LM serving: prefill/decode behind the fleet scheduler (a
    fleet of one)."""

    def __init__(self, cfg, *, batch: int, capacity: int, seed: int = 0,
                 mesh=None, eos_id: int | None = None, len_bucket: int = 16,
                 max_queue: int | None = None):
        assert cfg.embed_inputs, "serving driver expects token-input archs"
        self.cfg = cfg
        self.batch = batch
        self.capacity = capacity
        self.mesh = mesh or make_local_mesh()
        with shd.use_mesh(self.mesh, shd.SERVE_RULES):
            self.params = init_params(
                tfm.lm_schema(cfg), jax.random.PRNGKey(seed), cfg.dtype)
        self.backend = LMBackend(cfg, self.params, self.mesh,
                                 capacity=capacity, eos_id=eos_id,
                                 len_bucket=len_bucket)
        self.scheduler = FleetScheduler([self.backend], batch=batch,
                                        max_queue=max_queue)

    @property
    def outcomes(self) -> dict:
        """Per-request terminal outcomes of the last `serve` call."""
        return self.scheduler.outcomes

    @staticmethod
    def _legacy_stats(s: dict) -> dict:
        return {
            "prefill_s": s["start_s"],
            "decode_s": s["run_s"],
            "decode_steps": s["steps"],
            "new_tokens": s["emissions"],
            "decode_tok_s": s["emissions"] / max(s["run_s"], 1e-9),
            "finished": s["finished"],
            "backfills": s["backfills"],
        }

    def run_batch(self, requests: list[Request]) -> dict:
        """One run: the first ``batch`` requests are admitted, the rest
        backfill retired slots.  Returns timing stats.  Raises if a
        request can never join this run (capacity/context limits) — use
        `serve`, which gives leftovers a fresh run, for the general case."""
        queue = list(requests)
        stats = self.scheduler.run_once(queue)
        if queue:
            raise ValueError(
                f"{len(queue)} request(s) could not backfill into this "
                f"run (capacity/context limits); use serve()")
        return self._legacy_stats(stats)

    def serve(self, requests: list[Request]) -> list[dict]:
        """Bucket the queue by prompt length, then run batches with
        retirement + backfill until it drains (continuous batching)."""
        return [self._legacy_stats(s)
                for s in self.scheduler.serve(list(requests))]


# --------------------------------------------------------------------------
# CNN backend: SparseNet.apply on padded image batches
# --------------------------------------------------------------------------

class CNNBackend:
    """One-shot image backend: a request finishes in a single wave.

    Slot reuse across waves is the batch-reuse story — the compiled
    (width, H, W, C) executable from `models.graph.BatchedApply` serves
    every wave of a bucket.  ``image_size`` pins the bucket to the net's
    fixed input (Flatten-head nets like VGG); when None the bucket pads
    each image's H/W up to ``pad_multiple`` (size-agnostic nets like the
    GAP-headed ResNets).

    A partial wave (the tail of a drained queue) computes on a batch shrunk
    to the occupied slots — rounded up to the next power of two, capped at
    the full width — instead of padding with zero images that burn full
    sparse-path FLOPs.  The pow2 ladder bounds the compile count per shape
    bucket at log2(width)+1 executables.

    ``step`` is split into ``dispatch`` (build the padded batch, issue its
    transfer and the jitted apply — JAX async dispatch returns before the
    device finishes) and ``collect`` (block on the result).  As the
    backend is ``one_shot``, the scheduler dispatches each replica's wave
    k+1 before it collects wave k: wave k+1's batch build, transfer and
    launch overlap wave k's device work, and the device starts wave k+1
    with its input resident.  Each wave builds a fresh host batch, so no
    buffer is written while its transfer may be in flight.  One tick
    dispatches every replica's wave before collecting any, so replicas'
    device work overlaps.  ``mesh``/``rules`` flow to `BatchedApply`'s
    sharded compile path (sharded FC heads — see `ReplicaGroup`).

    While a profiler session is on, each wave records `launch.spans`:
    ``backend.stack`` (the host batch), ``backend.put`` (its transfer),
    ``backend.launch`` (the jitted call; ``miss`` when it compiled,
    ``xla_convs`` the conv layers it runs through XLA, not a kernel,
    ``dw_convs`` the depthwise conv layers it runs),
    ``backend.wait`` and ``backend.fetch`` (the result's copy to the host),
    and puts ``images`` (occupied slots) and ``rows`` (the batch computed)
    on the scheduler's ``backend.wave`` span.
    """

    one_shot = True

    def __init__(self, net, params, *, sparse=None, impl: str = "auto",
                 density: float | None = None, image_size: int | None = None,
                 pad_multiple: int = 8, mesh=None, rules=None):
        from repro.models.graph import (BatchedApply, input_refusal,
                                        output_finite)
        self.image_size = image_size
        self.pad_multiple = pad_multiple
        self.channels = next((l.cin for l in net.conv_layers()), None)
        self._input_refusal = input_refusal
        self._output_finite = output_finite
        self.apply = BatchedApply(net, params, sparse=sparse, impl=impl,
                                  key=(density,), mesh=mesh, rules=rules)

    # -- scheduler protocol -------------------------------------------------

    def validate_request(self, req: ImageRequest) -> str | None:
        """Admission-time validation via `models.graph.input_refusal`:
        malformed images (wrong type/rank/dtype, non-finite values,
        oversize for a fixed-input net) become structured refusals."""
        return self._input_refusal(req.image, max_size=self.image_size,
                                   channels=self.channels)

    def check_emission(self, emission) -> bool:
        """Output guard: non-finite logits quarantine the replica that
        produced them (`models.graph.output_finite`)."""
        return self._output_finite(emission)

    def reset(self, req: ImageRequest) -> None:
        req.out.clear()
        req.logits = None

    def bucket_key(self, req: ImageRequest):
        h, w, c = req.image.shape
        if self.image_size is not None:
            if max(h, w) > self.image_size:
                raise ValueError(
                    f"image {h}x{w} exceeds the net's fixed input size "
                    f"{self.image_size}")
            return (self.image_size, self.image_size, c)
        m = self.pad_multiple
        return (_round_up(h, m), _round_up(w, m), c)

    def sort_key(self, req: ImageRequest):
        return req.rid  # arrival order; all images in a bucket are equal

    def start(self, requests: list[ImageRequest], width: int):
        return {"width": width, "bucket": self.bucket_key(requests[0])}, None

    def dispatch(self, state, slots):
        """Issue one wave: pad the occupied slots into a batch and call the
        jitted apply.  The returned handle holds device arrays still in
        flight (JAX async dispatch) — `collect` blocks on them."""
        hb, wb, c = state["bucket"]
        occ = [j for j, r in enumerate(slots) if r is not None]
        # shrink a partial wave to the occupied slots (pow2 ladder): zero
        # images are no longer computed at full sparse-path cost
        nb = min(state["width"], 1 << max(len(occ) - 1, 0).bit_length())
        spans.annotate(images=len(occ), rows=nb)
        with spans.span("backend.stack"):
            x = np.zeros((nb, hb, wb, c), np.float32)
            for i, j in enumerate(occ):
                h, w, _ = slots[j].image.shape
                x[i, :h, :w] = slots[j].image
        with spans.span("backend.put", bytes=x.nbytes):
            x = jnp.asarray(x)
        with spans.span("backend.launch") as launch:
            compiles = self.apply.compiles
            y = self.apply(x)
            launch.set(miss=self.apply.compiles > compiles,
                       xla_convs=self.apply.xla_convs,
                       dw_convs=self.apply.dw_convs)
        return occ, y

    def collect(self, state, handle, slots):
        occ, y_dev = handle
        with spans.span("backend.wait"):
            y_dev.block_until_ready()
        with spans.span("backend.fetch"):
            y = np.asarray(y_dev)
        emis = [None] * state["width"]
        for i, j in enumerate(occ):
            emis[j] = y[i]
        return state, emis

    def step(self, state, slots):
        return self.collect(state, self.dispatch(state, slots), slots)

    def can_backfill(self, state, req: ImageRequest) -> bool:
        return self.bucket_key(req) == state["bucket"]

    def backfill(self, state, slot: int, req: ImageRequest):
        return state, None  # computed on the next wave

    def append(self, req: ImageRequest, logits) -> bool:
        req.logits = np.asarray(logits)
        req.out.append(int(req.logits.argmax()))
        return True

    def finish(self, state) -> dict:
        return {"compiles": self.apply.compiles}


class ReplicaGroup:
    """N data-parallel CNN backend replicas with device-placed weights.

    The available devices form a (data, model) grid: one device group per
    replica along ``data`` (replicas beyond the grid wrap around, so CPU
    tests run many replicas on one device), and — when ``shard_fc`` — a
    per-replica ``model`` axis over which the FC heads' output strips are
    sharded (`models.graph.shard_sparse`: each device computes its strip
    slice of the cout-sharded `vsmm`, GSPMD all-gathers the logits in the
    epilogue).  Each replica holds its own `jax.device_put` copy of the
    params and sparse trees, so each compiles an executable resident on
    its own devices and the scheduler's dispatch-all-then-collect tick
    overlaps the replicas' device work.
    """

    def __init__(self, net, params, *, sparse=None, impl: str = "auto",
                 density: float | None = None, image_size: int | None = None,
                 pad_multiple: int = 8, replicas: int = 1,
                 shard_fc: bool = False, rules=None, validate: bool = True):
        from repro.models import graph as G
        assert replicas >= 1
        if validate and image_size is not None:
            validate_net(net, image_size, density=density)
        self.replicas = replicas
        self.shard_fc = shard_fc
        self.rules = rules or shd.SERVE_RULES
        ndev = jax.device_count()
        model = max(1, ndev // replicas) if shard_fc else 1
        data = max(1, ndev // model)
        grid = np.array(jax.devices()[: data * model]).reshape(data, model)
        self.meshes: list = []
        self.backends: list[CNNBackend] = []
        for i in range(replicas):
            mesh = jax.sharding.Mesh(grid[i % data], ("model",))
            with shd.use_mesh(mesh, self.rules) as ctx:
                p_i = jax.device_put(
                    params, shd.named_sharding((), ctx=ctx))
                s_i = (None if sparse is None
                       else G.shard_sparse(sparse, ctx=ctx))
            self.meshes.append(mesh)
            self.backends.append(CNNBackend(
                net, p_i, sparse=s_i, impl=impl, density=density,
                image_size=image_size, pad_multiple=pad_multiple,
                mesh=mesh, rules=self.rules))


def validate_net(net, image_size: int, *, density: float | None = None,
                 vk: int = 32, vn: int = 128) -> None:
    """vscheck IR gate before any device placement: walk the net's shapes
    and tile geometry at the serving input size and refuse placement
    (`analysis.VSCheckError`) on structural errors — a malformed net
    otherwise fails mid-compile on one replica after the others already
    hold weights."""
    from repro.analysis.ir import check_net
    cin = next((l.cin for l in net.conv_layers()), 3)
    nc = check_net(net, (1, image_size, image_size, cin),
                   density=density if density is not None else 0.25,
                   vk=vk, vn=vn)
    nc.report.raise_errors()


class CNNServer:
    """Batched CNN serving: `SparseNet.apply` behind the fleet scheduler.

    ``cfg`` is a VSCNN config (`configs.vscnn_vgg16` / `vscnn_resnet18`):
    ``cfg.build()`` gives the `SparseNet`, ``cfg.weight_density`` the
    default pruning point.  ``sparse=False`` serves the dense jnp path (the
    XLA conv baseline the benchmarks compare against).

    One replica without ``shard_fc`` serves one `CNNBackend` over the
    server's own ``params``, with no device placement and no mesh.
    ``replicas > 1`` (or ``shard_fc``) serves a `ReplicaGroup` —
    device-placed weight copies, with the FC heads optionally
    cout-sharded over each replica's ``model`` devices.  ``fault_plan``
    wraps every replica in a `ChaosBackend`.
    """

    def __init__(self, cfg, *, batch: int, impl: str = "auto",
                 density: float | None = None, sparse: bool = True,
                 dtype: str | None = None,
                 seed: int = 0, pad_multiple: int = 8, replicas: int = 1,
                 shard_fc: bool = False, validate: bool = True,
                 fault_plan: FaultPlan | None = None,
                 max_queue: int | None = None,
                 deadline_waves: int | None = None, max_attempts: int = 3):
        self.cfg = cfg
        self.replicas = replicas
        self.fault_plan = fault_plan
        self.net = cfg.build()
        self.density = cfg.weight_density if density is None else density
        if validate:
            validate_net(self.net, cfg.image_size, density=self.density,
                         vk=cfg.vk, vn=cfg.vn)
        self.params = init_params(
            self.net.schema(), jax.random.PRNGKey(seed), jnp.float32)
        # ``pruned``: the dense param tree computing the same function as
        # ``sparse`` (BN folded, pruned, int8 dequantized) — the oracle a
        # caller compares the served logits against
        self.sparse = self.pruned = None
        if sparse:
            # dtype="int8" serves the compound sparsity x precision path:
            # per-cout power-of-two weight scales baked in at sparsify time,
            # activations quantized per-tensor at apply time
            self.sparse, self.pruned = self.net.sparsify(
                self.params, self.density, vk=cfg.vk, vn=cfg.vn, dtype=dtype)
        kw = dict(sparse=self.sparse, impl=impl,
                  density=self.density if sparse else None,
                  image_size=cfg.image_size if cfg.fixed_image_size else None,
                  pad_multiple=pad_multiple)
        if replicas > 1 or shard_fc:
            self.group = ReplicaGroup(self.net, self.params,
                                      replicas=replicas, shard_fc=shard_fc,
                                      validate=False, **kw)
            self.backends = self.group.backends
        else:
            self.backends = [CNNBackend(self.net, self.params, **kw)]
        if fault_plan is not None:
            self.backends = [ChaosBackend(b, fault_plan, replica=i)
                             for i, b in enumerate(self.backends)]
        self.backend = self.backends[0]
        self.scheduler = FleetScheduler(
            self.backends, batch=batch, max_queue=max_queue,
            deadline_waves=deadline_waves, max_attempts=max_attempts)

    @property
    def outcomes(self) -> dict:
        """Per-request terminal outcomes of the last `serve` call."""
        return self.scheduler.outcomes

    def serve(self, requests: list[ImageRequest]) -> list[dict]:
        stats = self.scheduler.serve(list(requests))
        for s in stats:
            s["images"] = s.pop("emissions")
        return stats


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def random_prompt_lengths(rng, n: int, max_len: int, lo: int = 8) -> list[int]:
    """n prompt lengths in [lo', max_len) with lo' clamped so the range is
    never empty — ``--prompt-len 8`` used to crash on integers(8, 8)."""
    if max_len < 2:
        raise ValueError(f"--prompt-len must be >= 2, got {max_len}")
    lo = max(1, min(lo, max_len - 1))
    return [int(rng.integers(lo, max_len)) for _ in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LM arch to serve")
    ap.add_argument("--cnn", default=None,
                    help="CNN arch to serve (e.g. vscnn-vgg16) instead of "
                         "an LM")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "jnp", "pallas", "pallas-halo",
                             "pallas-stack"],
                    help="CNN sparse path: auto = halo Pallas kernels on "
                         "TPU, structural jnp elsewhere")
    ap.add_argument("--replicas", type=int, default=1,
                    help="CNN data-parallel replica fleet size")
    ap.add_argument("--shard-fc", action="store_true",
                    help="cout-shard FC heads over each replica's model-"
                         "axis devices")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="LM sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="LM top-k truncation (0 = full vocab)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="CNN: inject a seeded FaultPlan "
                         "(deterministic chaos)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission depth (load shedding)")
    ap.add_argument("--deadline-waves", type=int, default=None,
                    help="CNN: per-request deadline in scheduler ticks")
    args = ap.parse_args()
    enable_compile_cache()
    if (args.arch is None) == (args.cnn is None):
        ap.error("choose exactly one of --arch (LM) or --cnn")

    rng = np.random.default_rng(0)
    if args.cnn:
        cfg = get_config(args.cnn).reduce()
        if getattr(cfg, "modality", "lm") != "cnn":
            ap.error(f"{cfg.name} is an LM arch; serve it with --arch")
        s = cfg.image_size
        reqs = [ImageRequest(
                    rid=i,
                    image=rng.standard_normal((s, s, 3)).astype(np.float32))
                for i in range(args.requests)]
        plan = (None if args.chaos_seed is None else FaultPlan.random(
            args.chaos_seed, replicas=max(args.replicas, 1)))
        srv = CNNServer(cfg, batch=args.batch, impl=args.impl,
                        replicas=args.replicas, shard_fc=args.shard_fc,
                        fault_plan=plan, max_queue=args.max_queue,
                        deadline_waves=args.deadline_waves)
        t0 = time.time()
        stats = srv.serve(reqs)
        wall = time.time() - t0
        tot = sum(st["images"] for st in stats)
        print(f"served {tot} images in {len(stats)} runs, "
              f"{tot / max(wall, 1e-9):.1f} img/s "
              f"(density {srv.density}, batch {args.batch}, "
              f"replicas {args.replicas}"
              f"{', shard-fc' if args.shard_fc else ''}"
              f"{f', chaos seed {args.chaos_seed}' if plan else ''})")
        outcomes = list(srv.outcomes.values())
        refused = [o for o in outcomes if o.status == "refused"]
        if plan is not None or refused:
            print(f"  outcomes: {len(outcomes) - len(refused)} delivered, "
                  f"{len(refused)} refused "
                  f"{sorted({o.reason for o in refused})}")
            if plan is not None:
                sch = srv.scheduler
                print(f"  plan: {plan.describe()}")
                print(f"  health: {sch.health}  "
                      f"faults fired: {len(sch.fault_events)}")
        for st in stats:
            print("  ", {k: (round(v, 4) if isinstance(v, float) else v)
                         for k, v in st.items()})
        return

    cfg = get_config(args.arch).reduce()
    if getattr(cfg, "modality", "lm") != "lm":
        ap.error(f"{cfg.name} is a CNN arch; serve it with --cnn")
    lens = random_prompt_lengths(rng, args.requests, args.prompt_len)
    reqs = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, lens[i], dtype=np.int32),
                max_new=args.tokens, temperature=args.temperature,
                top_k=args.top_k)
        for i in range(args.requests)
    ]
    srv = Server(cfg, batch=args.batch,
                 capacity=_round_up(args.prompt_len, 16) + args.tokens + 8,
                 eos_id=args.eos_id)
    stats = srv.serve(reqs)
    tot_new = sum(s["new_tokens"] for s in stats)
    tot_dec = sum(s["decode_s"] for s in stats)
    print(f"served {len(reqs)} requests in {len(stats)} runs: "
          f"{tot_new} tokens, {tot_new/max(tot_dec,1e-9):.1f} tok/s decode")
    for s in stats:
        print("  ", {k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in s.items()})


if __name__ == "__main__":
    main()

"""Cost model for packed LoRA fine-tuning jobs (paper §4 + Appendix A).

Memory follows Appendix A exactly: base weights + base activations (on the
max packed batch) + per-adapter params/grads/optimizer-moments/activations,
all divided by the parallelism degree d (TP sharding); a user load factor C
guards fragmentation.

Time is a three-term roofline per iteration — compute, HBM, interconnect —
so the paper's core observation (tiny batches underutilize hardware; packing
raises throughput at nearly constant cost) *emerges* from the model instead
of being hard-coded: at bs=1 the weight-traffic term dominates and packing
more adapters is almost free until the compute term takes over.

``calibrate`` fits a single efficiency scalar from a few profiled iterations
(the paper profiles 10 iterations on the testbed).

The estimation layer is pluggable: every consumer (DTM, knapsack, planner,
engine, cluster runner) programs against :class:`CostEstimator`; the analytic
roofline :class:`CostModel` below is the *prior* implementation, and
:class:`repro.sched.profile.ProfiledCostModel` layers measured segment
timings on top of it for real execution.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

from repro.configs.base import LoraConfig, ModelConfig


class CostEstimator:
    """Interface of the estimation layer (tentpole of the profile feedback
    loop): what the packing solver, DTM, planner, and execution engine are
    allowed to ask about a candidate packed job.

    Subclasses provide the three core queries — per-iteration time, memory
    feasibility, minimum degree — plus a ``setup_time`` attribute; the
    job-level queries below derive from those, so a subclass that changes
    ``iter_time`` (e.g. by consulting measured timings) automatically
    re-prices every downstream planning decision.

    The analytic :class:`CostModel` is the pure *prior*: deterministic,
    state-free, used by the virtual-clock simulator. The profiled layer
    (:class:`repro.sched.profile.ProfiledCostModel`) additionally implements
    the measurement-feedback hooks (``observe``/``observed``) and reports
    ``adaptive = True``, which switches the engine's real execution path to
    re-plan on live device-free events.
    """

    # ---------------- core queries (subclass responsibility) ----------------

    def iter_time(self, configs: Sequence[LoraConfig], d: int, seq: int) -> float:
        """Seconds per packed training iteration on ``d`` device units."""
        raise NotImplementedError

    def fits(self, configs: Sequence[LoraConfig], d: int, seq: int) -> bool:
        raise NotImplementedError

    def min_degree(self, configs: Sequence[LoraConfig], seq: int) -> Optional[int]:
        raise NotImplementedError

    # ---------------- derived job-level queries ----------------

    def job_time(
        self, configs: Sequence[LoraConfig], d: int, seq: int, n_steps: int
    ) -> float:
        return self.job_time_residual(configs, [n_steps] * len(configs), d, seq)

    def job_time_residual(
        self,
        configs: Sequence[LoraConfig],
        steps: Sequence[int],
        d: int,
        seq: int,
    ) -> float:
        """Per-job residual-step cost query (online engine): adapters resumed
        from a preempted job carry fewer remaining steps than fresh arrivals,
        and a packed job holds its devices until its longest-residual adapter
        finishes. ``steps[i]`` is the remaining iteration count of
        ``configs[i]``; the job pays setup once plus ``max(steps)``
        packed iterations."""
        if not configs:
            return self.setup_time
        return self.setup_time + max(steps) * self.iter_time(configs, d, seq)

    def adapter_finish_offset(
        self, configs: Sequence[LoraConfig], steps: int, d: int, seq: int
    ) -> float:
        """Seconds from job launch until an adapter with ``steps`` residual
        iterations is done training (it may ride along until the pack's
        longest adapter finishes, but its own weights stop changing here)."""
        return self.setup_time + steps * self.iter_time(configs, d, seq)

    def throughput(self, configs: Sequence[LoraConfig], d: int, seq: int) -> float:
        """Paper Eq (13): LoRA FLOP per unit time. LoRA FLOP is linear in
        rank (§2.1) and, with heterogeneous batch sizes, in rank * batch."""
        return sum(c.rank * c.batch_size for c in configs) / self.iter_time(
            configs, d, seq
        )

    # ---------------- measurement feedback (no-op for pure priors) ----------

    def observe(
        self,
        configs: Sequence[LoraConfig],
        d: int,
        seq: int,
        measured_iter_time: float,
    ) -> None:
        """Feed one measured per-iteration wall time back into the estimator.
        The analytic prior ignores it; the profiled layer folds it into its
        observation store."""

    def observed(self, configs: Sequence[LoraConfig], d: int, seq: int) -> bool:
        """Whether this exact (pack shape, degree, seq) has been measured."""
        return False

    # ---------------- heterogeneous fleets (class-blind by default) ---------

    #: estimators that price per host class (extra ``host_class=`` kwarg on
    #: iter_time/observe/observed/drift) advertise True; the engine only
    #: passes class tags when this is set
    class_aware = False

    def class_ratio(self, host_class: str, d: Optional[int] = None) -> float:
        """Measured slowdown of a host class vs this estimator's baseline
        (1.0 = unknown/identical) — placement ranking for heterogeneous
        fleets. Pure priors have no measurements: always 1.0."""
        return 1.0

    # ---------------- simulation contract ----------------

    @property
    def adaptive(self) -> bool:
        """True when real execution should re-plan against live measurements."""
        return False

    def virtual_model(self) -> "CostEstimator":
        """The pure prior used by the virtual-clock simulator — simulation
        must stay deterministic and independent of any measurement state."""
        return self


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    mem_bytes: float  # per device unit
    peak_flops: float  # per device unit (bf16)
    hbm_bw: float  # bytes/s per device unit
    link_bw: float  # bytes/s per link (TP collective)
    n_devices: int = 8
    efficiency: float = 0.5  # asymptotic fraction of peak in large GEMMs
    # tokens-per-device at which GEMM efficiency reaches half its asymptote —
    # THE paper effect: tiny per-device batches run far below peak (SM
    # occupancy 16.7%, §3.1), so adding packed adapters is nearly free until
    # the device saturates. eff(tpd) = efficiency * tpd / (tpd + sat_tokens).
    sat_tokens: float = 600.0
    # per-layer fixed overhead per iteration (kernel launch / dispatch /
    # framework); not divided by the parallelism degree. Calibrated so a
    # bs=1 short-seq iteration is overhead-dominated (paper §5.1: iteration
    # time grows only ~10% from bs 1 -> 8 on GLUE-scale sequences).
    layer_overhead: float = 12.5e-3
    # extra per-adapter per-iteration cost of the NAIVE sequential adapter
    # loop (paper §5.1: packing 8 adapters naively is 3.6x slower than one
    # adapter — small launches + low arithmetic intensity). PLoRA's packed
    # kernels eliminate this term.
    seq_adapter_overhead: float = 0.14

    def eff(self, tokens_per_device: float) -> float:
        t = max(tokens_per_device, 1.0)
        return self.efficiency * t / (t + self.sat_tokens)

    def scaled(self, **kw) -> "HardwareSpec":
        import dataclasses

        return dataclasses.replace(self, **kw)


# Presets: the paper's testbeds + our target. sat_tokens/layer_overhead are
# fitted to the paper's §5.1 anchors (see EXPERIMENTS.md §Calibration).
A100_40G = HardwareSpec("a100-40g", 40e9, 312e12, 2.0e12, 300e9, 8,
                        sat_tokens=600.0, layer_overhead=12.5e-3,
                        seq_adapter_overhead=0.14)
A10_24G = HardwareSpec("a10-24g", 24e9, 125e12, 0.6e12, 32e9, 8,
                       sat_tokens=300.0, layer_overhead=18e-3,
                       seq_adapter_overhead=0.2)
TPU_V5E = HardwareSpec("tpu-v5e", 16e9, 197e12, 819e9, 50e9, 256,
                       sat_tokens=1_500.0, layer_overhead=0.2e-3,
                       seq_adapter_overhead=0.01)

# the prior of each TPU, keyed by ``jax.Device.device_kind``
TPU_PRIORS = {"TPU v5 lite": TPU_V5E}


def tpu_prior(device_kind: str) -> HardwareSpec:
    """The prior for a TPU of this ``device_kind``; a TPU without one is an
    error, never some other chip's preset."""
    try:
        return TPU_PRIORS[device_kind]
    except KeyError:
        raise ValueError(
            f"no cost-model prior for TPU kind {device_kind!r} (known: "
            f"{sorted(TPU_PRIORS)})"
        ) from None


def _mla_shapes(a, d: int) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of each MLA projection: q through a low-rank pair
    (q_a, q_b) or, with q_lora_rank 0, direct."""
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    shapes = {"kv_a": (d, a.kv_lora_rank + a.qk_rope_head_dim),
              "kv_b": (a.kv_lora_rank, a.n_heads * (a.qk_nope_head_dim + a.v_head_dim)),
              "o": (a.n_heads * a.v_head_dim, d)}
    if a.q_lora_rank:
        shapes["q_a"] = (d, a.q_lora_rank)
        shapes["q_b"] = (a.q_lora_rank, a.n_heads * qk)
    else:
        shapes["q"] = (d, a.n_heads * qk)
    return shapes


def model_param_count(cfg: ModelConfig) -> float:
    """Total parameters (embeddings + stack), honest per-family accounting."""
    a = cfg.attention
    d = cfg.d_model
    total = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    for mixer, ffn in zip(cfg.layer_kinds(), cfg.ffn_kinds()):
        if mixer == "attn":
            if a.is_mla:
                total += sum(i * o for i, o in _mla_shapes(a, d).values())
            else:
                hd = a.head_dim
                total += d * hd * (a.n_heads + 2 * a.n_kv_heads) + a.n_heads * hd * d
        else:
            di = cfg.ssm.d_inner(d)
            n = cfg.ssm.d_state
            total += d * 2 * di + d * 2 * n + d * cfg.ssm.n_heads(d) + di * d
        if ffn == "dense":
            mats = 2 if cfg.mlp_kind == "gelu2" else 3
            total += mats * d * cfg.d_ff
        elif ffn == "moe":
            m = cfg.moe
            total += m.n_experts * 3 * d * m.d_expert + d * m.n_experts
            total += 3 * d * m.n_shared_experts * m.d_expert
    if cfg.is_encdec:
        mats = 2 if cfg.mlp_kind == "gelu2" else 3
        per_enc = d * a.head_dim * (a.n_heads + 2 * a.n_kv_heads) + a.n_heads * a.head_dim * d
        per_enc += mats * d * cfg.d_ff
        # decoder cross-attention blocks
        total += cfg.encoder_layers * per_enc
        total += cfg.n_layers * (d * a.head_dim * (a.n_heads + 2 * a.n_kv_heads) + a.n_heads * a.head_dim * d)
    return float(total)


def active_param_count(cfg: ModelConfig) -> float:
    """Parameters touched per token (MoE: top_k of E routed experts, and
    every shared expert)."""
    total = model_param_count(cfg)
    if cfg.moe.enabled:
        moe_layers = sum(1 for f in cfg.ffn_kinds() if f == "moe")
        expert_params = moe_layers * 3 * cfg.d_model * cfg.moe.d_expert
        total -= expert_params * (cfg.moe.n_experts - cfg.moe.top_k)
    return float(total)


def lora_param_count(cfg: ModelConfig, rank: int) -> float:
    """Packed-LoRA params for one adapter over cfg.lora_targets, layer by
    layer: an MoE layer's gate/up/down adapters sit on its shared experts
    (none without them; routed experts carry no adapter)."""
    a, d = cfg.attention, cfg.d_model
    attn = {
        "q": (d, a.n_heads * a.head_dim),
        "k": (d, a.n_kv_heads * a.head_dim),
        "v": (d, a.n_kv_heads * a.head_dim),
        "o": (a.n_heads * a.head_dim, d),
        "kv": (d, a.kv_lora_rank + a.qk_rope_head_dim),
        "ssm_in": (d, 2 * cfg.ssm.d_inner(d)),
        "ssm_out": (cfg.ssm.d_inner(d), d),
    }
    if a.is_mla:
        mla = _mla_shapes(a, d)
        attn["q"] = mla["q_a"] if a.q_lora_rank else mla["q"]
        attn["o"] = mla["o"]

    def mlp(width):
        return {"gate": (d, width), "up": (d, width), "down": (width, d)}

    def layer(ffn):
        shapes = dict(attn)
        if ffn == "dense":
            shapes.update(mlp(cfg.d_ff))
        elif ffn == "moe" and cfg.moe.n_shared_experts:
            shapes.update(mlp(cfg.moe.n_shared_experts * cfg.moe.d_expert))
        return sum(rank * (i + o) for t, (i, o) in shapes.items()
                   if t in cfg.lora_targets)

    total = sum(layer(f) for f in cfg.ffn_kinds())
    total += cfg.encoder_layers * layer("dense")
    return float(total)


@dataclass
class CostModel(CostEstimator):
    cfg: ModelConfig
    hw: HardwareSpec
    prec_bytes: int = 2  # bf16 training
    opt_factor: float = 3.0  # AdamW: grads + 2 moments (paper's c_grad)
    act_factor: float = 12.0  # activation bytes per (token x d_model), no remat
    load_factor: float = 0.9  # paper's C
    calib: float = 1.0  # fitted efficiency scalar
    # fixed per-adapter memory overhead (optimizer workspace, allocator
    # fragmentation, autograd bookkeeping). Fitted to the paper's §3.2 anchor:
    # +2.2 GB for the second adapter on Qwen-2.5-7B/A100-40G, "up to 10
    # concurrent adapters without OOM".
    adapter_overhead_bytes: float = 1.0e9
    # Padding-aware costing (beyond-paper, DESIGN.md §9): the packed executor
    # zero-pads every adapter to the pack's bucket rank (max rank rounded up
    # to 8), so a rank-8 adapter packed with a rank-128 one COMPUTES at rank
    # 128. With this flag the cost model charges the bucket rank, which makes
    # the DTM packer prefer rank-homogeneous packs. False = the paper's
    # padding-naive model (each adapter billed at its own rank).
    pad_aware: bool = True
    # Ragged-kernel accounting (kernels/ops.py rank segments): the kernels
    # group same-rank adapters into grid segments and compute each adapter at
    # its OWN rank (8-aligned), so mixed-rank packs stop paying bucket-
    # padding FLOPs. The autotuner's ``KernelProfile.calibrate`` sets this —
    # it supersedes pad_aware for the *time* model (memory stays bucketed:
    # the pack still allocates padded weights).
    ragged: bool = False
    # Measured LoRA-kernel rate scale (autotune feedback): the fused
    # base+delta megakernel's measured speedup over the two-pass formulation
    # on this backend. The LoRA compute term is divided by it — 1.0 = the
    # uncalibrated analytic prior (bit-identical to the pre-autotune model).
    lora_rate_scale: float = 1.0
    # Frozen-base storage scheme (kernels/quant.py): None keeps the dense
    # ``prec_bytes`` footprint (bit-identical to the pre-quant model);
    # "int8"/"nf4" shrink the base-weight term of the Appendix-A memory
    # model — and the HBM weight-traffic term of the roofline — to the
    # quantized bytes/param, which is what lets the knapsack packer put
    # more packs on a device (the planner-shift this tier claims).
    base_dtype: Optional[str] = None

    @staticmethod
    def bucket_rank(configs: Sequence[LoraConfig]) -> int:
        r = max((c.rank for c in configs), default=8)
        return max(8, (r + 7) // 8 * 8)

    def _eff_rank(self, c: LoraConfig, configs: Sequence[LoraConfig]) -> int:
        if self.ragged:
            return max(8, (c.rank + 7) // 8 * 8)
        return self.bucket_rank(configs) if self.pad_aware else c.rank

    # ---------------- memory (Appendix A) ----------------

    def base_bytes_per_param(self) -> float:
        """Resident bytes per frozen-base parameter under ``base_dtype``.

        Quantized schemes include the amortized f32 scale overhead: int8
        carries one scale per output channel (~1/256 of params on typical
        d_in >= 256 projections), nf4 one scale per 64-element block. The
        analytic constants are deliberately slightly conservative; the
        measured ratio on real quantized trees is what ``bench_quant``
        reports against the paper-claim threshold."""
        if self.base_dtype in (None, "f32", "bf16"):
            return float(self.prec_bytes)
        if self.base_dtype == "int8":
            return 1.0 + 4.0 / 256.0
        if self.base_dtype == "nf4":
            return 0.5 + 4.0 / 64.0
        raise ValueError(f"unknown base_dtype {self.base_dtype!r}")

    def base_weight_bytes(self) -> float:
        return model_param_count(self.cfg) * self.base_bytes_per_param()

    def base_act_bytes(self, total_batch: int, seq: int) -> float:
        return (
            self.act_factor * total_batch * seq * self.cfg.d_model * self.prec_bytes
        )

    def lora_bytes(self, c: LoraConfig, seq: Optional[int] = None) -> float:
        seq = seq or c.seq_len
        p = lora_param_count(self.cfg, c.rank) * self.prec_bytes
        grads_opt = self.opt_factor * p
        act = c.batch_size * seq * c.rank * self.prec_bytes * (
            self.cfg.n_layers + self.cfg.encoder_layers
        )
        return p + grads_opt + act + self.adapter_overhead_bytes

    def job_mem_bytes(self, configs: Sequence[LoraConfig], d: int, seq: int) -> float:
        total_batch = sum(c.batch_size for c in configs)
        base = self.base_weight_bytes() + self.base_act_bytes(total_batch, seq)
        if self.pad_aware:
            import dataclasses as _dc

            rb = self.bucket_rank(configs)
            loras = sum(
                self.lora_bytes(_dc.replace(c, rank=rb), seq) for c in configs
            )
        else:
            loras = sum(self.lora_bytes(c, seq) for c in configs)
        return (base + loras) / d

    def fits(self, configs: Sequence[LoraConfig], d: int, seq: int) -> bool:
        return self.job_mem_bytes(configs, d, seq) <= (
            self.load_factor * self.hw.mem_bytes
        )

    def min_degree(self, configs: Sequence[LoraConfig], seq: int) -> Optional[int]:
        d = 1
        while d <= self.hw.n_devices:
            if self.fits(configs, d, seq):
                return d
            d *= 2
        return None

    # ---------------- time (three-term roofline) ----------------

    def iter_time(self, configs: Sequence[LoraConfig], d: int, seq: int) -> float:
        """Seconds per packed training iteration on d device units."""
        tokens = sum(c.batch_size for c in configs) * seq
        n_active = active_param_count(self.cfg)
        # frozen base: fwd 2ND + act-grad bwd 2ND = 4ND
        base_flops = 4.0 * n_active * tokens
        # padding-aware: each adapter computes at the pack's bucket rank
        lora_flops = sum(
            6.0 * lora_param_count(self.cfg, self._eff_rank(c, configs))
            * c.batch_size * seq
            for c in configs
        )
        # per-device GEMM granularity shrinks with TP degree: tokens don't
        # split under TP but each device's slice of every GEMM does, so the
        # efficiency argument is tokens/d (penalizes Max-GPU, §7.2.1).
        eff = self.hw.eff(tokens / d)
        # lora_rate_scale is the autotuner's measured fused-kernel speedup
        # (1.0 = uncalibrated; division by 1.0 is bit-exact, so the default
        # model is unchanged)
        compute_t = (base_flops + lora_flops / self.lora_rate_scale) / (
            d * self.hw.peak_flops * eff
        )
        # weight traffic: weights read in fwd + bwd; adapters updated
        wbytes = 2.0 * self.base_weight_bytes()
        wbytes += sum(
            (2.0 + 2.0 * self.opt_factor)
            * lora_param_count(self.cfg, c.rank)
            * self.prec_bytes
            for c in configs
        )
        act_bytes = 2.0 * self.base_act_bytes(
            sum(c.batch_size for c in configs), seq
        )
        mem_t = (wbytes + act_bytes) / (d * self.hw.hbm_bw)
        # TP collectives: 2 all-reduces of (tokens, d_model) per layer, ring
        coll_t = 0.0
        if d > 1:
            layer_count = self.cfg.n_layers + self.cfg.encoder_layers
            coll_bytes = (
                4.0  # fwd+bwd, attn+mlp
                * layer_count
                * tokens
                * self.cfg.d_model
                * self.prec_bytes
                * 2.0
                * (d - 1)
                / d
            )
            coll_t = coll_bytes / (d * self.hw.link_bw)
        fixed_t = self.hw.layer_overhead * (
            self.cfg.n_layers + self.cfg.encoder_layers
        )
        return (max(compute_t, mem_t) + coll_t + fixed_t) * self.calib

    def iter_time_sequential(
        self, configs: Sequence[LoraConfig], d: int, seq: int
    ) -> float:
        """Naive packed execution (paper §5.1 / Fig. 6 'Sequential PLoRA'):
        the BASE pass is batched over all adapters' inputs, but each adapter's
        LoRA computation runs as its own small kernel sequence — per-adapter
        launch overhead plus LoRA GEMMs at single-adapter efficiency.
        (Calls CostModel.iter_time explicitly so subclasses that alias
        iter_time -> iter_time_sequential don't recurse.)"""
        t = CostModel.iter_time(self, configs, d, seq)
        for c in configs:
            tokens_k = c.batch_size * seq
            lora_flops = 6.0 * lora_param_count(self.cfg, c.rank) * tokens_k
            t += self.calib * (
                self.hw.seq_adapter_overhead
                + lora_flops / (d * self.hw.peak_flops * self.hw.eff(tokens_k / d))
            )
        return t

    # per-job fixed cost: base-checkpoint load + process/compile warmup.
    # Min-GPU pays it once per CONFIG (120x); packed jobs amortize it —
    # this is the planner-only gain visible in the Fig. 6 ablation.
    setup_time: float = 60.0

    # job_time / job_time_residual / adapter_finish_offset / throughput are
    # inherited from CostEstimator, derived from iter_time + setup_time.

    # ---------------- calibration ----------------

    def calibrate(self, measured_iter_time: float, configs, d: int, seq: int):
        """Fit the time scalar so predicted == measured (one-point fit from
        ~10 profiled iterations, as in the paper)."""
        pred = self.iter_time(configs, d, seq)
        self.calib = self.calib * measured_iter_time / pred
        return self.calib

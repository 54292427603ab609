"""Observability: throughput/MFU computed in-train + structured logging.

The reference computes throughput from the tqdm rate and MFU offline
(SURVEY.md 5.1, 5.5); here both are first-class: model FLOPs from the
config, per-device peak FLOPs from the device kind, metrics appended to a
JSONL file in the rundir (wandb optional, gated on import)."""

from __future__ import annotations

import json
import os
import time
import typing as tp

import jax

from midgpt_tpu.config import ExperimentConfig, ModelConfig, to_dict

# bf16 peak FLOP/s per chip, keyed by a substring of ``device_kind``
# (spaces dropped, lower case; "TPU v5 lite" is the v5e). Source: Google
# Cloud TPU documentation, the "System architecture" page of each
# generation (v5e: 197 TFLOP/s).
_PEAK_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


class UnknownDevicePeak(LookupError):
    """The device has no entry in the peak table (the CPU, a new chip).
    A utilization against an assumed chip is a made-up number, so there
    is no default: callers that only log say "not measured"."""


def device_peak_flops(device: tp.Optional[jax.Device] = None) -> float:
    device = device or jax.devices()[0]
    kind = device.device_kind.lower().replace(" ", "")
    if device.platform == "tpu":
        for key, val in _PEAK_FLOPS.items():
            if key in kind:
                return val
    raise UnknownDevicePeak(
        f"no peak FLOP/s for {device.platform} device kind "
        f"{device.device_kind!r}"
    )


def flops_per_token(model: ModelConfig, seq_len: tp.Optional[int] = None) -> float:
    """Training FLOPs/token (fwd+bwd), PaLM-style 6N + attention term."""
    t = seq_len or model.block_size
    d = model.n_embd
    c = model.head_dim
    from midgpt_tpu.models.gpt import mlp_hidden_dim

    f = mlp_hidden_dim(model)
    # parameter FLOPs (2 per MAC, x3 for fwd+bwd)
    qkv = d * (model.n_head + 2 * model.kv_heads) * c
    proj = model.n_head * c * d
    mlp = (3 if model.mlp == "swiglu" else 2) * d * f
    per_layer = qkv + proj + mlp
    # + the lm-head projection only: the token embedding is a gather (or a
    # one-hot contraction of the same cost class under TP), not counted
    n_matmul = model.n_layer * per_layer + d * model.vocab_size
    param_flops = 6 * n_matmul
    # attention score/value FLOPs: 2 matmuls of T x C per head, causal ~1/2
    attn_flops = 6 * 2 * model.n_layer * model.n_head * c * t  # per token
    attn_flops = attn_flops / 2  # causal
    return param_flops + attn_flops


def mfu(tokens_per_sec: float, model: ModelConfig, n_devices: int) -> float:
    """Model FLOP/s utilization against this device's peak; raises
    :class:`UnknownDevicePeak` where there is none to divide by."""
    achieved = tokens_per_sec * flops_per_token(model)
    peak = device_peak_flops() * n_devices
    return achieved / peak


def decode_flops_per_token(
    model: ModelConfig, live_tokens: tp.Optional[float] = None
) -> float:
    """Inference FLOPs per generated token (forward only): 2 FLOPs per
    parameter MAC plus the attention score/value term over the live KV
    context — the numerator of serving MFU, which bench_serving records
    next to the HBM-floor attainment so the compute-vs-bandwidth split
    of a decode step is visible in one row."""
    live = float(model.block_size if live_tokens is None else live_tokens)
    d = model.n_embd
    c = model.head_dim
    from midgpt_tpu.models.gpt import mlp_hidden_dim

    f = mlp_hidden_dim(model)
    qkv = d * (model.n_head + 2 * model.kv_heads) * c
    proj = model.n_head * c * d
    mlp = (3 if model.mlp == "swiglu" else 2) * d * f
    n_matmul = model.n_layer * (qkv + proj + mlp) + d * model.vocab_size
    # scores + value sum: 2 matmuls of live x C per head, 2 FLOPs/MAC
    attn = 4 * model.n_layer * model.n_head * c * live
    return 2 * n_matmul + attn


def train_floor(
    cfg: ExperimentConfig, n_devices: int
) -> tp.Optional[tp.Dict[str, tp.Any]]:
    """The training-step roofline context MetricLogger attaches to every
    logging step (analysis/traffic.train_floor_decomposition, wired to
    this device's peak FLOPs): compute + HBM floors and the
    tokens-per-step needed to turn a measured tokens_per_sec into
    step_ms and an attainment fraction. None when the analytic floor
    doesn't cover the config (e.g. MoE) or the device has no known
    peak (the CPU) — logging then proceeds without the attainment keys
    rather than with wrong ones."""
    from midgpt_tpu.analysis.traffic import train_floor_decomposition

    try:
        return train_floor_decomposition(
            cfg.model,
            batch_size=cfg.batch_size,
            n_devices=n_devices,
            flops_per_token=flops_per_token(cfg.model),
            peak_flops_per_device=device_peak_flops(),
        )
    except (AssertionError, UnknownDevicePeak):
        return None


def moe_router_metrics(stats: tp.Mapping[str, tp.Any]) -> tp.Dict[str, float]:
    """Schema for the per-eval-interval MoE router telemetry (VERDICT r5
    Next #7): ``moe/aux`` (load-balance aux, 1.0 = perfectly balanced,
    summed over layers like the training loss term) and
    ``moe/dropped_frac`` (fraction of routing claims past expert
    capacity — the silent failure mode: dropped tokens ride the residual
    and never show in the loss curve). ``stats`` is
    ``models.gpt.GPT.moe_stats``'s output."""
    return {
        "moe/aux": float(stats["aux"]),
        "moe/dropped_frac": float(stats["dropped_frac"]),
    }


def _load_or_create_wandb_id(rundir: str, wandb_mod) -> tp.Optional[str]:
    """Read rundir/wandb_id.txt, creating it with a fresh id on first run
    (parity: /root/reference/launch.py:60-67). Returns None when the rundir
    isn't a writable local path (wandb then picks its own id)."""
    if not rundir:
        return None
    from midgpt_tpu.utils.fsio import open_path, path_exists

    path = os.path.join(rundir, "wandb_id.txt")
    try:
        if path_exists(path):
            with open_path(path) as f:
                return f.read().strip()
        run_id = wandb_mod.util.generate_id()
        with open_path(path, "w") as f:
            f.write(run_id)
        return run_id
    except Exception:
        return None


class MetricLogger:
    """JSONL metrics + optional wandb, process-0 only (parity:
    launch.py:38-68 / train.py:212-213 wandb logging).

    ``floor`` (a ``train_floor`` dict) arms roofline attainment: any
    logged metrics dict carrying ``tokens_per_sec`` is augmented with
    ``step_ms`` (tokens_per_step / rate), the static
    ``train_hbm_floor_ms`` / ``train_compute_floor_ms`` decomposition,
    and ``train_attainment_frac = floor / measured`` — so the logged
    series reads against the hardware ceiling next to MFU instead of
    requiring hand arithmetic in PERF.md."""

    def __init__(
        self, rundir: str, config: ExperimentConfig,
        use_wandb: bool = False,
        floor: tp.Optional[tp.Mapping[str, tp.Any]] = None,
    ):
        self.is_main = jax.process_index() == 0
        self.floor = floor
        self._file = None
        self._wandb = None
        if not self.is_main:
            return
        if rundir and not rundir.startswith("gs://"):
            os.makedirs(rundir, exist_ok=True)
            self._file = open(os.path.join(rundir, "metrics.jsonl"), "a")
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb
                # persist the run id in the rundir so a resumed run
                # continues the same wandb run instead of forking a new one
                # (parity: /root/reference/launch.py:60-67)
                run_id = _load_or_create_wandb_id(rundir, wandb)
                wandb.init(
                    dir=rundir or None,
                    config=to_dict(config),
                    id=run_id,
                    resume="allow",
                )
            except Exception:
                self._wandb = None

    def attainment(
        self, tokens_per_sec: float
    ) -> tp.Dict[str, float]:
        """The roofline keys for one measured rate (empty without a
        floor context): measured step_ms, the two static floors, and
        attainment = floor / measured."""
        fl = self.floor
        if not fl or tokens_per_sec <= 0:
            return {}
        step_ms = fl["tokens_per_step"] / tokens_per_sec * 1e3
        return {
            "step_ms": round(step_ms, 3),
            "train_hbm_floor_ms": fl["train_hbm_floor_ms"],
            "train_compute_floor_ms": fl["train_compute_floor_ms"],
            # significant digits, not decimals: CPU attainment is ~1e-8
            # and must not round to a hard zero
            "train_attainment_frac": float(
                f"{fl['train_floor_ms_per_step'] / step_ms:.3g}"
            ),
        }

    def log(self, step: int, metrics: tp.Mapping[str, float]) -> None:
        if not self.is_main:
            return
        if "tokens_per_sec" in metrics:
            metrics = {
                **metrics, **self.attainment(metrics["tokens_per_sec"])
            }
        rec = {"step": step, "time": time.time(), **metrics}
        if self._file is not None:
            self._file.write(json.dumps(rec) + "\n")
            self._file.flush()
        if self._wandb is not None:
            self._wandb.log(metrics, step=step)

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
        if self._wandb is not None:
            self._wandb.finish()

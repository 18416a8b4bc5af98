"""Benchmark: LLaMA causal-LM training throughput on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
The reference publishes no absolute numbers (BASELINE.md), so vs_baseline is
reported against the driver-tracked north-star proxy: achieved model FLOPs
utilization (MFU) as a fraction of the 40% target on this chip.

A run that finds no TPU, or a TPU whose peak is not in the table below,
fails with a non-zero exit: it neither falls back to the CPU nor assumes a
peak (ROADMAP S1 rebuilds this script around benchmark cells).

- default config is a 7B-PROXY: the real LLaMA-7B layer shape
  (h=4096, inter=11008, heads=32, vocab=32000, seq=2048) with as many layers
  as fit one chip's HBM (OOM-adaptive search), fp32 master params + AdamW.
- besides the measured MFU, an EXTRAPOLATED 7B MFU is reported from a
  two-point fit t(L) = a + b*L over two layer counts — labeled as
  extrapolated, with the fit recorded.
- every successful run writes a BENCH_SELF_<ts>.json artifact (full details
  + HLO kernel provenance).

Integrity (VERDICT r1 weak #5 / item 10):
- peak TFLOP/s derived from the attached device kind (not hard-coded),
- FLOP count includes attention (6*N*T + 12*L*B*S^2*H, causal x0.5),
- the metric name carries the config; the JSON carries the real measured
  parameter count and which numbers are measured vs extrapolated,
- the compiled step's HLO is inspected to report which Pallas kernels
  (tpu_custom_call, by kernel name) the step really contains.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# bf16 peak TFLOP/s per chip by device kind (public spec sheets)
_PEAK_BF16_TFLOPS = {
    "TPU v2": 46.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,   # v6e / Trillium
    "TPU v6e": 918.0,
    "TPU v7": 4614.0,
}

_LLAMA_7B = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                 num_attention_heads=32)


def _peak_tflops(device) -> tuple[float, str]:
    kind = getattr(device, "device_kind", "") or ""
    if device.platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU; jax found {device.platform!r} "
                         f"({kind or 'unknown kind'})")
    for key, val in sorted(_PEAK_BF16_TFLOPS.items(), key=lambda kv: -len(kv[0])):
        if kind.startswith(key):
            return val, kind
    raise SystemExit(f"no bf16 peak known for device kind {kind!r}; add it to "
                     "_PEAK_BF16_TFLOPS with its source")


def _kernel_provenance(step, batch) -> list:
    """Pallas kernels in the HLO of the EXACT benchmarked train step."""
    from paddle_tpu.ops.pallas._common import kernel_names
    return sorted(set(kernel_names(step.lower_text(batch))))


def _is_oom(e: Exception) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s.upper()[:4000]
            or "Failed to allocate" in s)


def _build_and_time(cfg_kwargs, layers, batch, seq, n_steps=20,
                    warmup=3, fused_loss=False) -> dict:
    """Build the compiled train step for one (layers, batch) point and time
    it.  Raises on OOM (caller adapts)."""
    import jax

    import paddle_tpu as P
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   build_hybrid_train_step)

    P.seed(0)
    cfg = LlamaConfig(num_hidden_layers=layers,
                      max_position_embeddings=seq, **cfg_kwargs)
    model = LlamaForCausalLM(cfg)
    opt = P.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = build_hybrid_train_step(model, opt, n_microbatches=1, remat=True,
                                   amp=True, fused_loss=fused_loss)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq + 1))
    b = {"input_ids": P.to_tensor(ids[:, :-1]),
         "labels": P.to_tensor(ids[:, 1:])}

    last = {}

    def run_blocked(n):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step(b)
        jax.block_until_ready((loss._value, step.state["params"]))
        dt = time.perf_counter() - t0
        last["loss"] = float(loss.numpy())
        return dt

    run_blocked(warmup)  # compile + steady state
    dt = min(run_blocked(n_steps), run_blocked(n_steps)) / n_steps

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    kernels = _kernel_provenance(step, b)
    # free the model/optimizer state before the caller builds the next point
    del step, model, opt
    return {"layers": layers, "batch": batch, "seq": seq,
            "step_time_s": dt, "n_params": n_params,
            "loss": last["loss"], "kernels": kernels}


def _flops_per_step(n_params, layers, batch, seq, hidden):
    """6ND matmul FLOPs + causal attention FLOPs (fwd 4*B*S^2*H per layer for
    QK^T+PV, x3 fwd+bwd, x0.5 causal sparsity)."""
    tokens = batch * seq
    matmul = 6.0 * n_params * tokens
    attn = 12.0 * layers * batch * seq * seq * hidden * 0.5
    return matmul + attn


def _emit(payload: dict, detail: dict | None = None):
    print(json.dumps(payload), flush=True)
    if detail is not None:
        ts = time.strftime("%Y%m%d_%H%M%S")
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            f"BENCH_SELF_{ts}.json")
        try:
            with open(path, "w") as f:
                json.dump({**payload, "detail": detail}, f, indent=1)
            print(f"# artifact -> {path}", file=sys.stderr)
        except OSError as e:
            print(f"# artifact write failed: {e}", file=sys.stderr)


def main():
    config = os.environ.get("PT_BENCH_CONFIG", "7b_proxy")

    import jax

    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    peak, kind = _peak_tflops(dev)

    if config == "382m":
        cfg_kwargs = dict(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4128, num_attention_heads=16)
        candidates = [(10, 16, 1024)]
    elif config == "tiny":  # script-logic smoke config
        cfg_kwargs = dict(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_attention_heads=4)
        candidates = [(2, 2, 64)]
    else:  # 7b_proxy: true LLaMA-7B layer geometry, OOM-adaptive depth
        cfg_kwargs = dict(_LLAMA_7B)
        candidates = [(4, 2, 2048), (3, 2, 2048), (2, 2, 2048),
                      (2, 1, 2048), (1, 1, 2048)]

    # 7b_proxy defaults to the fused lm-head+CE Pallas kernel (skips the
    # [B*S, 32k] logits + cotangent buffers); PT_BENCH_FUSED_LOSS=0 reverts
    fused = (config == "7b_proxy"
             and os.environ.get("PT_BENCH_FUSED_LOSS", "1") == "1")
    meas = None
    oom_log = []
    for layers, batch, seq in candidates:
        try:
            meas = _build_and_time(cfg_kwargs, layers, batch, seq,
                                   fused_loss=fused)
            break
        except Exception as e:  # noqa: BLE001
            if _is_oom(e):
                oom_log.append(f"L={layers},B={batch}: OOM")
                print(f"# L={layers},B={batch},S={seq}: OOM, shrinking",
                      file=sys.stderr)
                continue
            raise
    if meas is None:
        raise SystemExit("out of device memory at every candidate: "
                         + "; ".join(oom_log))

    h = cfg_kwargs["hidden_size"]
    dt = meas["step_time_s"]
    tokens_per_sec = meas["batch"] * meas["seq"] / dt
    flops = _flops_per_step(meas["n_params"], meas["layers"], meas["batch"],
                            meas["seq"], h)
    achieved = flops / dt / 1e12
    mfu = achieved / peak

    detail = {"device": kind, "peak_bf16_tflops": peak, "config": config,
              "fused_loss": fused,
              "measured": meas, "achieved_tflops": round(achieved, 2),
              "mfu": round(mfu, 4), "oom_log": oom_log}

    extrap = None
    if config == "7b_proxy" and meas["layers"] > 1:
        # two-point fit t(L) = a + b*L -> honest 32-layer extrapolation
        l2 = max(1, meas["layers"] // 2)
        try:
            meas2 = _build_and_time(cfg_kwargs, l2, meas["batch"],
                                    meas["seq"], n_steps=10,
                                    fused_loss=fused)
            b_fit = (dt - meas2["step_time_s"]) / (meas["layers"] - l2)
            a_fit = dt - b_fit * meas["layers"]
            t32 = a_fit + 32 * b_fit
            layer_params = ((meas["n_params"] - meas2["n_params"])
                            / (meas["layers"] - l2))
            n_7b = meas["n_params"] + (32 - meas["layers"]) * layer_params
            f32 = _flops_per_step(n_7b, 32, meas["batch"], meas["seq"], h)
            extrap = {
                "label": "EXTRAPOLATED (not measured): 32-layer LLaMA-7B "
                         "from linear two-point fit t(L)=a+b*L on one chip",
                "fit_points": {f"L{meas['layers']}": dt,
                               f"L{l2}": meas2["step_time_s"]},
                "fit_a_s": a_fit, "fit_b_s_per_layer": b_fit,
                "t32_s": t32, "n_params_7b": int(n_7b),
                "extrapolated_7b_mfu": round(f32 / t32 / 1e12 / peak, 4),
                "extrapolated_7b_tokens_per_sec":
                    round(meas["batch"] * meas["seq"] / t32, 1),
            }
            detail["extrapolated_7b"] = extrap
        except Exception as e:  # noqa: BLE001 — extrapolation is best-effort
            detail["extrapolation_error"] = str(e)[:300]

    payload = {
        "metric": f"llama_7b_proxy_L{meas['layers']}_train_tokens_per_sec_per_chip"
        if config == "7b_proxy"
        else f"llama_{meas['n_params']/1e6:.0f}M_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "mfu": round(mfu, 4),
        "n_params_measured": meas["n_params"],
        "kernels": meas["kernels"],
    }
    if extrap is not None:
        payload["extrapolated_7b_mfu"] = extrap["extrapolated_7b_mfu"]
    _emit(payload, detail if config != "tiny" else None)
    print(f"# device={kind} peak={peak}TFLOP/s "
          f"params={meas['n_params']/1e6:.1f}M L={meas['layers']} "
          f"B={meas['batch']} S={meas['seq']} step={dt*1000:.1f}ms "
          f"achieved={achieved:.1f}TFLOP/s mfu={mfu*100:.1f}% "
          f"kernels={','.join(meas['kernels'])} loss={meas['loss']:.3f}",
          file=sys.stderr)


if __name__ == "__main__":
    main()

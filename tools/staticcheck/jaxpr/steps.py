"""Canonical captured steps the jaxpr tier traces.

The AST tier scans source; this module produces the *programs* the rules
run over: each canonical step is traced through the repo's own capture
machinery (jit/capture.py) exactly the way production code builds it —
TrainStep on the proxy llama, the serving batch-slot decode and
speculative-verify steps, and a to_static program — so the findings are
about what actually lowers, not a synthetic re-trace.

Every step is captured TWICE with equivalent fresh inputs. A second
lowering (or a fallback call) on value-equal avals is the signature-churn
form of the recompile hazard: something non-aval (a fresh closure, a
python scalar, an unhashable static) leaked into the cache key.

``PT_STATICCHECK_STEPS=/path/to/module.py`` swaps the canonical set for a
module exposing ``collect() -> list[StepResult]`` (the known-answer
fixture projects use this; ``trace_step`` below is the helper they build
on). Models are deliberately tiny — this is a linter, not a benchmark.
"""
from __future__ import annotations

import dataclasses
import inspect
import os
import runpy
from typing import Callable, List, Optional

STEPS_ENV = "PT_STATICCHECK_STEPS"


@dataclasses.dataclass
class StepResult:
    """One traced canonical step, ready for the rules."""
    name: str
    anchor_path: str          # project-root-relative file to report against
    anchor_line: int          # pragma line: `# staticcheck: ok[rule]` here
    program: object = None    # GraftProgram (None when capture failed)
    churn: bool = False       # re-lowered / fell back on equivalent inputs
    error: str = ""           # capture-bailout reason when program is None


def _anchor(obj, root: str):
    try:
        path = os.path.relpath(inspect.getsourcefile(obj), root)
        line = inspect.getsourcelines(obj)[1]
        return path.replace(os.sep, "/"), line
    except Exception:  # noqa: BLE001 — builtins/C callables: best effort
        return "<unknown>", 1


def trace_step(name: str, fn: Callable, make_args: Callable[[], tuple],
               *, root: str, donate="off", passes=None,
               allow_baked_rng: bool = True,
               anchor=None) -> StepResult:
    """Capture ``fn`` twice via capture_step with fresh equivalent args
    from ``make_args()``; returns the StepResult the rules consume."""
    from paddle_tpu.jit import capture

    path, line = _anchor(anchor if anchor is not None else fn, root)
    wrapper = capture.capture_step(fn, donate=donate, passes=passes,
                                   allow_baked_rng=allow_baked_rng)
    try:
        wrapper(*make_args())
        wrapper(*make_args())
    except Exception as e:  # noqa: BLE001 — a crashing step is a bailout
        return StepResult(name, path, line,
                          error=f"{type(e).__name__}: {e}"[:200])
    info = wrapper.cache_info()
    programs = wrapper.programs()
    if not programs:
        return StepResult(name, path, line,
                          error=wrapper.bailout_reason()
                          or "capture produced no program")
    return StepResult(name, path, line, program=programs[0],
                      churn=info["lowerings"] != 1)


# ---------------------------------------------------------------------------
# the canonical set
# ---------------------------------------------------------------------------

def _tiny_llama():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=2,
                           inter=64, seq=16)
    return cfg, LlamaForCausalLM(cfg)


def _train_step(root: str) -> StepResult:
    """TrainStep on the proxy llama — the lower_step path (donation via
    donate_argnums, shardings None on the single-device proxy)."""
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.jit import capture
    from paddle_tpu.parallel import trainer as trainer_mod

    path, line = _anchor(trainer_mod.TrainStep._build, root)
    try:
        P.seed(1234)
        cfg, model = _tiny_llama()
        opt = P.optimizer.AdamW(learning_rate=1e-3,
                                parameters=model.parameters())
        step = trainer_mod.compile_train_step(
            model,
            lambda m, b: m.compute_loss(b["input_ids"], b["labels"]), opt)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, size=(2, 8)).astype("int32")
        batch = {"input_ids": P.to_tensor(ids), "labels": P.to_tensor(ids)}
        step(batch)
        before = capture.capture_info()
        step(batch)  # equivalent avals: must ride the captured executable
        after = capture.capture_info()
    except Exception as e:  # noqa: BLE001 — a build failure is a bailout
        return StepResult("trainstep/llama", path, line,
                          error=f"{type(e).__name__}: {e}"[:200])
    prog = step.captured_program
    if prog is None:
        return StepResult("trainstep/llama", path, line,
                          error=capture.capture_info()["last_bailout"]
                          or "lower_step fell back to plain jit")
    churn = after["fallback_calls"] > before["fallback_calls"] \
        or after["lowerings"] > before["lowerings"]
    return StepResult("trainstep/llama", path, line, program=prog,
                      churn=churn)


def _serving_steps(root: str) -> List[StepResult]:
    """The engine's batch-slot decode step and the speculative verify
    step, captured exactly as inference/serving builds them (KV caches
    donated, per-slot offsets)."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.models import llama as llama_mod
    from paddle_tpu.models.steps import build_step

    try:
        P.seed(1234)
        cfg, model = _tiny_llama()
        B, W = 2, 3
        params = [p._value for p in model.parameters()]

        def cache_args():
            return [(kc._value, vc._value) for kc, vc in
                    model.init_kv_caches(B, cfg.max_position_embeddings)]

        tok = jnp.asarray(np.zeros((B, 1), np.int32))
        win = jnp.asarray(np.zeros((B, W), np.int32))
        off = jnp.zeros((B,), jnp.int32)
        last = jnp.zeros((B,), jnp.int32)

        out = []
        out.append(_wrapped_result(
            "serving/slot_step", build_step(model, "slot"), root,
            model.slot_step_body,
            lambda: (params, tok, cache_args(), off, last)))
        out.append(_wrapped_result(
            "serving/verify_step", build_step(model, "verify"), root,
            model.verify_step_body,
            lambda: (params, win, cache_args(), off)))
        return out
    except Exception as e:  # noqa: BLE001 — a build failure is a bailout
        path, line = _anchor(llama_mod.LlamaForCausalLM, root)
        err = f"{type(e).__name__}: {e}"[:200]
        return [StepResult("serving/slot_step", path, line, error=err),
                StepResult("serving/verify_step", path, line, error=err)]


def _wrapped_result(name: str, wrapper, root: str, anchor,
                    make_args) -> StepResult:
    """Drive an already-built CapturedStep twice (the model step builders
    pick their own donate config) and package the result."""
    path, line = _anchor(anchor, root)
    try:
        wrapper(*make_args())
        wrapper(*make_args())
    except Exception as e:  # noqa: BLE001
        return StepResult(name, path, line,
                          error=f"{type(e).__name__}: {e}"[:200])
    info = getattr(wrapper, "cache_info", lambda: {})()
    programs = getattr(wrapper, "programs", lambda: [])()
    if not programs:
        reason = getattr(wrapper, "bailout_reason", lambda: "")()
        return StepResult(name, path, line,
                          error=reason or "capture produced no program "
                                "(step fell back to the eager tier)")
    return StepResult(name, path, line, program=programs[0],
                      churn=info.get("lowerings", 1) != 1)


def _deepfm_step(root: str) -> StepResult:
    """The recommendation workload: DeepFM training through the sharded
    embedding tables (distributed/embedding) — on a dp2 mesh when this
    host has >= 2 devices (the exchange path: unique -> id all_to_all ->
    gather -> wire return must be fully comm-pass tagged), dense dp1
    otherwise. The lint gate is the 'zero new naked collectives' half of
    the subsystem's acceptance."""
    import jax
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import capture
    from paddle_tpu.models import deepfm as deepfm_mod
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel import trainer as trainer_mod

    path, line = _anchor(deepfm_mod.DeepFM, root)
    prev_mesh = mesh_mod.get_mesh()
    try:
        mesh = None
        if len(jax.devices()) >= 2:
            mesh = mesh_mod.init_mesh({"dp": 2}, devices=jax.devices()[:2])
        else:
            mesh_mod.set_mesh(None)
        P.seed(1234)
        model = deepfm_mod.DeepFM(
            sparse_feature_number=32, sparse_feature_dim=4,
            dense_feature_dim=4, sparse_field_num=4, layer_sizes=(16,))
        opt = P.optimizer.SGD(learning_rate=0.05,
                              parameters=model.parameters())
        step = trainer_mod.compile_train_step(
            model,
            lambda m, b: nn.functional.binary_cross_entropy_with_logits(
                m(b["sparse"], b["dense"]), b["y"]),
            opt, mesh=mesh)
        rng = np.random.RandomState(0)
        raw = {"sparse": rng.randint(0, 32, (8, 4)),
               "dense": rng.randn(8, 4).astype(np.float32),
               "y": (rng.rand(8, 1) > 0.5).astype(np.float32)}

        def batch():
            return {k: P.to_tensor(v.copy()) for k, v in raw.items()}

        step(batch())
        before = capture.capture_info()
        step(batch())  # equivalent avals: must ride the captured executable
        after = capture.capture_info()
    except Exception as e:  # noqa: BLE001 — a build failure is a bailout
        return StepResult("trainstep/deepfm-sharded-embedding", path, line,
                          error=f"{type(e).__name__}: {e}"[:200])
    finally:
        mesh_mod.set_mesh(prev_mesh)
    prog = step.captured_program
    if prog is None:
        return StepResult("trainstep/deepfm-sharded-embedding", path, line,
                          error=capture.capture_info()["last_bailout"]
                          or "lower_step fell back to plain jit")
    churn = after["fallback_calls"] > before["fallback_calls"] \
        or after["lowerings"] > before["lowerings"]
    return StepResult("trainstep/deepfm-sharded-embedding", path, line,
                      program=prog, churn=churn)


def _supervised_steps(root: str) -> List[StepResult]:
    """The elastic supervisor's TrainStep swap leg
    (distributed/supervisor.swap_train_step): capture the step at the
    PRE-swap mesh shape, drive the single-controller reshard the
    supervisor runs at every resume, and re-capture at the POST-swap
    shape — both programs must lint clean, or a scale event would trade a
    healthy step for a hazardous one mid-run. dp2 -> dp1 when this host
    has >= 2 devices, dp1 -> dp1 (still a full drop + re-lower) otherwise."""
    import jax
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.distributed import supervisor as sv_mod
    from paddle_tpu.jit import capture
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel import trainer as trainer_mod

    path, line = _anchor(sv_mod.swap_train_step, root)
    prev_mesh = mesh_mod.get_mesh()
    names = ("supervisor/trainstep-pre-swap",
             "supervisor/trainstep-post-swap")
    try:
        n_pre = 2 if len(jax.devices()) >= 2 else 1
        P.seed(1234)
        mesh_pre = mesh_mod.init_mesh({"dp": n_pre},
                                      devices=jax.devices()[:n_pre])
        model = P.nn.Linear(8, 4)
        opt = P.optimizer.SGD(learning_rate=0.1,
                              parameters=model.parameters())

        def loss_fn(m, b):
            x, y = b
            return P.nn.functional.mse_loss(m(P.to_tensor(x)),
                                            P.to_tensor(y))

        step = trainer_mod.compile_train_step(model, loss_fn, opt,
                                              mesh=mesh_pre)
        rng = np.random.RandomState(0)
        batch = (rng.randn(8, 8).astype(np.float32),
                 rng.randn(8, 4).astype(np.float32))

        results = []
        for name in names:
            if name == names[1]:
                # build the post-swap mesh HERE, not before the loop:
                # init_mesh installs the global mesh, and the pre-swap
                # capture must run with the dp{n_pre} mesh current
                sv_mod.swap_train_step(step, mesh_mod.init_mesh(
                    {"dp": 1}, devices=jax.devices()[:1]))
            step(batch)
            before = capture.capture_info()
            step(batch)  # equivalent avals: must ride the captured step
            after = capture.capture_info()
            prog = step.captured_program
            if prog is None:
                results.append(StepResult(
                    name, path, line,
                    error=capture.capture_info()["last_bailout"]
                    or "lower_step fell back to plain jit"))
                continue
            churn = after["fallback_calls"] > before["fallback_calls"] \
                or after["lowerings"] > before["lowerings"]
            results.append(StepResult(name, path, line, program=prog,
                                      churn=churn))
        return results
    except Exception as e:  # noqa: BLE001 — a build failure is a bailout
        err = f"{type(e).__name__}: {e}"[:200]
        return [StepResult(n, path, line, error=err) for n in names]
    finally:
        mesh_mod.set_mesh(prev_mesh)


def _to_static_step(root: str) -> StepResult:
    """A to_static-compiled layer — the jit.api lower_step path."""
    import numpy as np

    import paddle_tpu as P
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import api as jit_api

    path, line = _anchor(jit_api.StaticFunction._build, root)
    try:
        P.seed(1234)
        model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                              nn.Linear(16, 4))
        static = P.to_static(model)
        x = np.random.RandomState(1).randn(4, 8).astype(np.float32)
        static(P.to_tensor(x))
        static(P.to_tensor(x.copy()))
        sf = model._static_function
    except Exception as e:  # noqa: BLE001 — a build failure is a bailout
        return StepResult("to_static/mlp", path, line,
                          error=f"{type(e).__name__}: {e}"[:200])
    progs = [e[0].captured_program for e in sf.concrete_programs
             if getattr(e[0], "captured_program", None) is not None]
    if not progs:
        return StepResult("to_static/mlp", path, line,
                          error="to_static compile did not capture "
                                "(lower_step fell back to plain jit)")
    return StepResult("to_static/mlp", path, line, program=progs[0],
                      churn=len(sf.concrete_programs) != 1)


def _force_cpu():
    """A linter must never grab the accelerator, whatever JAX_PLATFORMS the
    caller exported: force the platform at config level."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — backend already initialized: keep it
        pass


def canonical_steps(root: str) -> List[StepResult]:
    """Trace the repo's canonical steps on the CPU backend."""
    _force_cpu()
    results = [_train_step(root)]
    results += _serving_steps(root)
    results.append(_to_static_step(root))
    results.append(_deepfm_step(root))
    results += _supervised_steps(root)
    return results


def load_steps(root: str,
               steps_file: Optional[str] = None) -> List[StepResult]:
    """The canonical set, or the module named by PT_STATICCHECK_STEPS /
    ``steps_file`` (must expose ``collect(root) -> list[StepResult]``)."""
    target = steps_file or os.environ.get(STEPS_ENV)
    if target:
        _force_cpu()
        mod = runpy.run_path(target)
        return list(mod["collect"](root))
    return canonical_steps(root)

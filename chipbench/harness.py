"""One run of one benchmark cell: set-up, measured window, checks.

Everything a cell needs is found by name: the workload entry in
``BENCHMARK.json``, its configuration ``configs/<config>.json`` with the
plain reference ``references/<reference>.py`` it names, its traffic mix
``traffic/<traffic>.json``, and one reader ``metrics/<metric>.py`` per
metric.  The system under test is ``src/repro``; the harness takes only
its serving entry points, its restore statistics and the kernel's name
from it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import costs, trace as tracing
from chipbench.traffic import Traffic, seed_key

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
OUT = REPO / ".chipbench"  # run outputs (traces); listed in .gitignore
TIMEOUT_S = 300.0          # longest wait for one request's answer
CHECKED = 8                # (function, prompt) pairs compared with the reference
CONTROLS = ("int8", "altered")  # see compare_with_reference


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ loading
def load_json(path: Path) -> Dict:
    return json.loads(path.read_text())


def benchmark() -> Dict:
    return load_json(REPO / "BENCHMARK.json")


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "chipbench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def cell_metrics(bench: Dict, cell: str) -> Tuple[List[Dict], List[Dict]]:
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if cell in m.get("workloads", [cell] if m["moves"] in moved else [])]
    return e2e, per_layer


def reader(name: str) -> Callable:
    return load_module(HERE / "metrics" / f"{name}.py").read


def reference(config: Dict):
    return load_module(HERE / "references" / f"{config['reference']}.py")


def program_config(config: Dict, ref, dm):
    """The served program's ModelConfig for ``config``: the program's
    preset with the file's overrides, checked field by field against the
    sizes the reference reads from the same file."""
    from repro.configs import get_config
    from repro.serve.instance import layer_sequence

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog.get("overrides", {}))
    for k, v in ref.program_fields(dm).items():
        if getattr(cfg, k) != v:
            raise ValueError(f"program runs {k}={getattr(cfg, k)!r}, "
                             f"the configuration states {v!r}")
    for spec in layer_sequence(cfg):
        for k, v in ref.LAYER.items():
            if getattr(spec, k) != v:
                raise ValueError(f"program layer {spec} has {k}={getattr(spec, k)!r}")
    return cfg


# ------------------------------------------------------------------ weights
def weights_dtype(config: Dict):
    """The type the weights are served in: the source's ``torch_dtype``."""
    import jax.numpy as jnp

    return jnp.dtype(config["torch_dtype"])


def base_weights(ref, dm, seed: int, dtype):
    """The base weights, made on the device in one jitted call."""
    import jax

    return jax.jit(lambda k: ref.init_params(dm, k, dtype))(
        jax.random.PRNGKey(seed_key(seed)))


def finetune(params, scale: float, layers: int, vocab: int):
    """A delta fine-tune of the stacked ``params``: the top 40% of the
    layers and the first 1/512 of the embedding rows scale by
    ``1 + scale``, the final norm shifts by ``scale``; the rest is the base
    byte for byte, so it restores from the base's pages."""
    import jax

    cut, rows = int(layers * 0.6), max(1, vocab // 512)

    def bump(p):
        out = dict(p)
        out["final_norm"] = p["final_norm"] + scale
        out["embed"] = dict(p["embed"])
        out["embed"]["tok"] = p["embed"]["tok"].at[:rows].multiply(1.0 + scale)
        if "unembed" in p["embed"]:
            out["embed"]["unembed"] = p["embed"]["unembed"] * (1.0 + scale)
        out["pattern"] = tuple(
            jax.tree.map(lambda a: a.at[cut:].multiply(1.0 + scale), blk)
            for blk in p["pattern"])
        return out

    return jax.jit(bump)(params)


# ------------------------------------------------------------ instruments
class CompileCounter:
    """Compiles that JAX asked for (persistent-cache lookups and backend
    compiles) while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.requests = self.backend = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if self.on and event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    @property
    def count(self) -> int:
        return max(self.requests, self.backend)


class StreamCapture:
    """Keeps the residual stream the served head receives, once per
    (function, prompt): the timed path's own output, read after the
    window.  Wraps ``repro.serve.instance._head_fn`` for the run."""

    def __init__(self):
        self.key = None
        self.got: Dict[Tuple[int, int], Any] = {}

    @contextlib.contextmanager
    def installed(self):
        from repro.serve import instance

        real = instance._head_fn

        def head_fn(cfg):
            fn = real(cfg)

            def call(p_embed, p_norm, x):
                key = self.key
                if key is not None and key not in self.got:
                    self.got[key] = x
                return fn(p_embed, p_norm, x)

            return call

        instance._head_fn = head_fn
        try:
            yield self
        finally:
            instance._head_fn = real


class OverlayLog:
    """Page plans of the overlay patches dispatched while ``on``."""

    def __init__(self):
        self.on = False
        self.calls: List[Tuple[Any, int]] = []

    @contextlib.contextmanager
    def installed(self):
        from repro.kernels.overlay_patch import ops

        real = ops.overlay_patch_device

        def patched(base, priv, kinds, src):
            if self.on:
                self.calls.append((kinds, int(np.prod(base.shape[1:])) * base.dtype.itemsize))
            return real(base, priv, kinds, src)

        ops.overlay_patch_device = patched
        try:
            yield self
        finally:
            ops.overlay_patch_device = real

    def plans(self) -> List[Tuple[np.ndarray, int]]:
        return [(np.asarray(k), pb) for k, pb in self.calls]


# --------------------------------------------------------------------- run
@dataclasses.dataclass
class Request:
    function: int
    prompt: int
    ttft_s: float
    cold: bool = False
    joined: bool = False
    token: Optional[int] = None
    restore_s: Optional[float] = None
    upload_s: Optional[float] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cell: Dict
    config: Dict
    mix: Dict
    requests: List[Request]
    window_s: float
    setup_s: float
    flops_per_request: float
    device_kind: str
    trace: Optional[tracing.Trace] = None
    overlay_plans: Optional[List[Tuple[np.ndarray, int]]] = None

    def done(self, kind: Optional[str] = None) -> List[Request]:
        """Requests answered, of one kind ("cold" or "warm") or all."""
        ok = [r for r in self.requests if r.error is None]
        if kind == "cold":
            return [r for r in ok if r.cold and not r.joined]
        if kind == "warm":
            return [r for r in ok if not r.cold]
        return ok

    def peaks(self) -> Dict:
        return costs.peaks(self.device_kind)


def device_check(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return devs[0], len(devs)


def _invocation(cfg, fname: str, prompt: np.ndarray, mix: Dict):
    from repro.serve.invocation import Invocation, QosClass

    return Invocation(function=fname, prompt=prompt[None], cfg=cfg,
                      max_new_tokens=int(mix["max_new_tokens"]),
                      qos=QosClass(mix["qos"]))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             bench: Optional[Dict] = None, config: Optional[Dict] = None,
             mix: Optional[Dict] = None, control: bool = False) -> Dict:
    """Run cell ``name`` once and return the result line's object.
    ``config`` and ``mix`` replace the files' contents (tests only).  With
    ``control``, the result also holds, under "control", the compared
    numbers of each control of :func:`compare_with_reference`."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import FixedTTLPolicy, ServerlessNode

    bench = bench or benchmark()
    cell = workload(bench, name)
    chip, n_chips = device_check(cell["chips"], require_tpu)
    enable_compile_cache()
    compiles = CompileCounter()
    config = config or load_json(REPO / config_entry(bench, cell["config"])["file"])
    mix = mix or load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    ref = reference(config)
    dm = ref.dims(config)
    cfg = program_config(config, ref, dm)
    dtype = weights_dtype(config)
    traffic = Traffic(mix, seed, dm.vocab)
    n_fn = int(mix["functions"])
    step = float(config["serving"]["finetune_scale_step"])
    scales = [step * (i + 1) for i in range(n_fn)]
    fnames = [f"ft{i}" for i in range(n_fn)]

    phases: Dict[str, float] = {}
    tick = time.perf_counter()

    def phase(label: str) -> None:
        nonlocal tick
        now = time.perf_counter()
        phases[label] = now - tick
        tick = now

    # ---------------------------------------------------------------- set-up
    params = base_weights(ref, dm, seed, dtype)
    nbytes = serve.image_bytes(params)
    keepalive = FixedTTLPolicy(float(mix["keep_alive_s"])) if mix["keep_alive_s"] else None
    node = ServerlessNode(
        install=config["serving"]["install"], name="bench",
        # base on host and on device, one publish's scratch, the warm
        # instances and staging: no reclaim inside the window
        memory_budget_bytes=(3 + n_fn) * nbytes, keepalive=keepalive,
    )
    capture, overlays = StreamCapture(), OverlayLog()
    requests: List[Request] = []
    with contextlib.ExitStack() as stack:
        workdir = stack.enter_context(tempfile.TemporaryDirectory(prefix="chipbench-"))
        stack.callback(node.close)
        stack.enter_context(capture.installed())
        stack.enter_context(overlays.installed())
        serve.install_base(node, cfg, params)
        for fname, scale in zip(fnames, scales):
            tuned = finetune(params, scale, dm.layers, dm.vocab)
            node.publish(fname, cfg, tuned, workdir, base_name=serve.BASE_IMAGE,
                         formats=("jif",))
            del tuned
        del params
        gc.collect()
        phase("publish")

        def ask(fi: int, pi: int):
            return node.submit_invocation(_invocation(
                cfg, fnames[fi], traffic.prompts[pi], mix)).result(timeout=TIMEOUT_S)

        # warm every shape the window uses: one restore of each function
        # (cold mixes drop it again at once; warm mixes keep it warm)
        for fi in range(n_fn):
            ask(fi, 0)
        # residual tails stream in the background: land them before the window
        node.scheduler.drain_residual(TIMEOUT_S)
        logdir = None
        if trace:
            logdir = OUT / "trace" / f"{name}-{seed}"
            if logdir.exists():
                shutil.rmtree(logdir)
            logdir.mkdir(parents=True)
            # runtime host events and the harness's spans, no Python tracer:
            # it would slow the host path the window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(logdir), profiler_options=opts)
        phase("warm_up")
        setup_s = time.perf_counter() - t_process

        # ---------------------------------------------------------- window
        order = traffic.requests()
        compiles.on, overlays.on = True, True
        t_start = time.perf_counter()
        deadline = t_start + seconds
        with TraceAnnotation(tracing.WINDOW_SPAN):
            while time.perf_counter() < deadline:
                fi, pi = next(order)
                capture.key = (fi, pi)
                with TraceAnnotation("chipbench.request"):
                    t0 = time.perf_counter()
                    try:
                        r = ask(fi, pi)
                        t1 = time.perf_counter()
                        st = r.stats or {}
                        requests.append(Request(
                            fi, pi, t1 - t0, cold=r.cold, joined=r.joined,
                            token=int(np.asarray(r.tokens)[0, 0]),
                            restore_s=st.get("total_s"), upload_s=st.get("upload_s")))
                    except Exception as exc:  # noqa: BLE001 — counted as failed
                        requests.append(Request(fi, pi, time.perf_counter() - t0,
                                                error=repr(exc)))
        t_end = time.perf_counter()
        compiles.on, overlays.on = False, False
        capture.key = None
        if trace:
            jax.profiler.stop_trace()

        memory_peak = (chip.memory_stats() or {}).get("peak_bytes_in_use")
        phase("window")
        # a restore's device tree can outlive its request in reference
        # cycles; collect them before the comparisons allocate
        gc.collect()
        leaves_differ = restore_check(node, cfg, ref, dm, seed, dtype, fnames, scales,
                                      traffic, mix, keep_warm=bool(mix["warm_in_setup"]))
    del node
    gc.collect()
    phase("restore_check")

    run = Run(cell=cell, config=config, mix=mix, requests=requests,
              window_s=t_end - t_start, setup_s=setup_s,
              flops_per_request=ref.flops(dm, int(mix["prompt_len"])),
              device_kind=chip.device_kind)
    served = sample_served(run.done(), capture.got, seed)
    capture.got.clear()
    numbers = compare_with_reference(ref, dm, seed, dtype, scales, traffic, served)
    phase("reference")
    expect = mix["expect"]

    def checks_of(gaps: Dict[str, float]) -> Dict[str, Tuple[float, float]]:
        return {
            "failed": (len(requests) - len(run.done()), 0),
            f"not_{expect}": (len(run.done()) - len(run.done(expect)), 0),
            "compiles_in_window": (compiles.count, 0),
            "restore_leaves_differ": (leaves_differ, 0),
            **{k: (gaps[k], config["limits"][k]) for k in ("token_gap", "stream_gap")},
        }

    def passes(checks) -> bool:
        return all(v <= lim for v, lim in checks.values())

    checks = checks_of(numbers)
    low = None
    if control:
        low = {}
        for kind in CONTROLS:
            gaps = compare_with_reference(ref, dm, seed, dtype, scales, traffic, served,
                                          control=kind)
            low[kind] = dict(gaps, correct=passes(checks_of(gaps)))
    del served
    e2e, per_layer = cell_metrics(bench, name)
    device = {"platform": chip.platform, "kind": chip.device_kind,
              "count": n_chips, "memory_peak_bytes": memory_peak}
    out: Dict[str, Any] = {}
    if trace:
        run.trace = tracing.load(str(logdir))
        run.overlay_plans = overlays.plans()
        summ = tracing.summary(run.trace)
        device["busy_s"], device["window_s"] = summ["busy_s"], summ["window_s"]
        out["breakdown"] = summ["breakdown"]
        print("trace layout: " + json.dumps(run.trace.layout), file=sys.stderr)
        shutil.rmtree(logdir, ignore_errors=True)
        phase("trace_read")
    print("phases: " + json.dumps(phases), file=sys.stderr)
    metrics = {}
    for m in (per_layer if trace else e2e):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": passes(checks),
        "attempted": len(requests),
        "failed": checks["failed"][0],
        "metrics": metrics,
        "device": device,
        **out,
        **({"control": low} if low is not None else {}),
        "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()},
    }
    return result


def restore_check(node, cfg, ref, dm, seed, dtype, fnames, scales, traffic, mix,
                  keep_warm: bool) -> int:
    """Leaves of restored device trees that differ from the published
    weights, over every function.  Warm mixes compare the instances the
    window served; cold mixes restore each function once more through the
    same node, kept warm for the comparison.  One function's expected
    weights are on the device at a time, compared layer by layer in place."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import FixedTTLPolicy

    if not keep_warm:
        node.scheduler.keepalive = FixedTTLPolicy(300.0)
    equal = jax.jit(lambda stacked, i, got: jax.tree.map(
        lambda s, g: jnp.array_equal(s[i], g), stacked, got))

    def same(got, exp, stacked: bool) -> bool:
        shape = exp.shape[1:] if stacked else exp.shape
        return (isinstance(got, jax.Array) and got.shape == shape
                and got.dtype == exp.dtype)

    differ = 0
    for fname, scale in zip(fnames, scales):
        gc.collect()
        if not keep_warm:
            node.submit_invocation(_invocation(
                cfg, fname, traffic.prompts[0], mix)).result(timeout=TIMEOUT_S)
        node.scheduler.drain_residual(TIMEOUT_S)
        # the same two programs that made the published weights
        base = base_weights(ref, dm, seed, dtype)
        want = finetune(base, scale, dm.layers, dm.vocab)
        del base
        stacked = want["pattern"][0]
        inst = node.scheduler.instance(fname)
        with inst.pinned_warm_tree() as tree:
            if (jax.tree.structure(tree["embed"]) != jax.tree.structure(want["embed"])
                    or len(tree["layers"]) != dm.layers
                    or any(jax.tree.structure(t) != jax.tree.structure(stacked)
                           for t in tree["layers"])):
                raise RuntimeError(f"{fname}: restored tree structure differs")
            for got, exp in [*zip(jax.tree.leaves(tree["embed"]), jax.tree.leaves(want["embed"])),
                             (tree["final_norm"], want["final_norm"])]:
                ok = same(got, exp, False) and bool(jnp.array_equal(got, exp))
                differ += 0 if ok else 1
            for i, layer in enumerate(tree["layers"]):
                gots, exps = jax.tree.leaves(layer), jax.tree.leaves(stacked)
                shaped = [same(g, e, True) for g, e in zip(gots, exps)]
                eq = jax.tree.leaves(equal(stacked, i, layer)) if all(shaped) else shaped
                differ += sum(0 if (s and bool(e)) else 1 for s, e in zip(shaped, eq))
        del want, stacked
        node.evict(fname)
    return differ


def sample_served(done: List[Request], streams: Dict, seed: int,
                  n: int = CHECKED) -> Dict:
    """A sample, drawn from the seed, of the (function, prompt) pairs the
    window answered: every token served for them and the residual stream
    captured for each."""
    pairs = sorted({(r.function, r.prompt) for r in done})
    rng = np.random.default_rng([seed, 2])
    picked = [pairs[i] for i in sorted(rng.permutation(len(pairs))[:n])]
    tokens: Dict[Tuple[int, int], List[int]] = {k: [] for k in picked}
    for r in done:
        if (r.function, r.prompt) in tokens:
            tokens[(r.function, r.prompt)].append(r.token)
    return {"tokens": tokens, "streams": {k: streams[k] for k in picked if k in streams}}


def quantize_int8(params):
    """Weight matrices rounded to int8 with one symmetric scale per output
    channel (the embedding: per row), held in bfloat16; vectors bfloat16."""
    import jax
    import jax.numpy as jnp

    def q(path, a):
        a = a.astype(jnp.float32)
        if a.ndim == 3:  # stacked (layers, in, out)
            axis = -2
        elif "tok" in jax.tree_util.keystr(path):  # embedding (vocab, d)
            axis = -1
        else:
            return a.astype(jnp.bfloat16)
        s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-12) / 127.0
        return (jnp.clip(jnp.round(a / s), -127, 127) * s).astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(q, params)


def compare_with_reference(ref, dm, seed, dtype, scales, traffic, served: Dict,
                           control: Optional[str] = None) -> Dict[str, float]:
    """The numbers that decide ``correct``, against the float32 reference
    at HIGHEST matmul precision, computed one prompt at a time:

    token_gap   widest gap by which a served token's logit lies below the
                reference's best logit at the last prompt position;
    stream_gap  widest such gap, over every position of each captured
                residual stream, of the token that stream's logits put
                first (the stream is scored by the reference's own head).

    ``served`` (see :func:`sample_served`) holds the tokens served for each
    (function, prompt) and the residual stream captured for it.  A
    ``control`` puts another computation in the program's place: "int8",
    the reference with its weights rounded to int8 per output channel and
    bfloat16 activations, the next precision below the configuration's
    bfloat16 weights; or "altered", every served token shifted by one id."""
    import jax
    import jax.numpy as jnp

    ref_layers = jax.jit(lambda p, t: ref.layers(dm, p, t))
    ref_head = jax.jit(lambda p, x: ref.head(dm, p, x))
    low_layers = jax.jit(lambda p, t: ref.layers(dm, p, t, jnp.bfloat16, None))
    low_head = jax.jit(lambda p, x: ref.head(dm, p, x, None))

    @jax.jit
    def stream_gap(ref_logits, x, p):
        logits = ref.head(dm, p, x.astype(jnp.float32))
        first = jnp.argmax(logits, -1)
        got = jnp.take_along_axis(ref_logits, first[:, None], -1)[:, 0]
        return jnp.max(ref_logits.max(-1) - got)

    base = base_weights(ref, dm, seed, dtype)
    tok_gap, str_gap = 0.0, 0.0
    keys = sorted(set(served["tokens"]) | set(served["streams"]))
    for fi in sorted({k[0] for k in keys}):
        p = finetune(base, scales[fi], dm.layers, dm.vocab)
        low_p = quantize_int8(p) if control == "int8" else p
        for key in [k for k in keys if k[0] == fi]:
            prompt = jnp.asarray(traffic.prompts[key[1]])
            ref_logits = ref_head(p, ref_layers(p, prompt))
            last = np.asarray(ref_logits[-1])
            tokens = served["tokens"].get(key, [])
            stream = served["streams"].get(key)
            if control == "int8":
                low = low_layers(low_p, prompt)
                tokens = [int(jnp.argmax(low_head(low_p, low)[-1]))] if tokens else []
                stream = low if stream is not None else None
            elif control == "altered":
                tokens = [(t + 1) % dm.vocab for t in tokens]
            for t in tokens:
                tok_gap = max(tok_gap, float(last.max() - last[t]))
            if stream is not None:
                x = stream.reshape(-1, stream.shape[-1])
                str_gap = max(str_gap, float(stream_gap(ref_logits, x, p)))
            del ref_logits
        del p, low_p
    return {"token_gap": tok_gap, "stream_gap": str_gap}

"""The benchmark harness: builds one cell, serves its traffic on the wall
clock through the program's streaming server, and reads the window.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json``             -- the cell's configuration and traffic names
* ``chipbench/configs/<c>.json`` -- the model configuration as it is run
* ``chipbench/traffic/<t>.json`` -- the traffic mix's parameters
* ``chipbench/cells/<cell>.json`` -- slots, cache, offered rate, warm-up,
  the correctness sample and its limit
* ``chipbench/metrics/<m>.py``   -- one reader per per-layer metric
* ``chipbench/<r>.py``           -- the configuration's plain reference,
  named by the configuration's ``"reference"`` key (``reference`` when it
  has none)

A reference module describes a family of models from the configuration
alone and imports nothing of the program. Each function takes the model
dict ``m`` (``Cell.model``):

* ``layer_shapes(m)`` (required): the leaf names and shapes of a layer, as
  the program's parameter tree names them under ``layers`` (``attn.wq.w``;
  [out, in] for a projection). One dict where every layer is alike, else a
  list of one dict per layer. The program's layout must match it.
* ``gaps(m, seed, prompts, served, control=False)`` (required): per
  sequence, a dict with ``gap`` (the widest gap of a served token's logit
  below the reference's best), ``tokens``, ``agree`` (served tokens that are
  the reference's first choice) and, with ``control``, ``control_gap`` (the
  same for the token the lower-precision control puts first).
* ``leaf(seed, name, layer, shape, dtype)`` (optional): the seeded draw of
  a leaf ``chipbench/weights.py`` has no rule for, such as a stack of
  experts drawn expert by expert through ``weights.leaf``.
* ``projection_flops(m)`` (optional): the required matmul operations of one
  position over all layers. Without it ``chipbench/flops.py`` counts two
  per weight of every 2-D leaf of ``layer_shapes``, kept weights only for
  the leaves named in the module's ``SPARSE``.
* ``attention_flops(m, context)`` (optional): one position attending
  ``context`` positions over all layers, linear in ``context``. Without it
  ``flops.py`` counts softmax attention over ``n_heads`` heads.

From the program it takes only the system under test (``serving.api``,
the model's parameter layout and the weight reformat tool), its counters
(every numeric field of ``SchedulerMetrics``, so a field a later program
adds reaches a reader as it is: its change over the window in
``rec["delta"]``, its readings at the window's open and close, as a gauge
is read, in ``rec["counters"]``) and, in a traced run, its spans
(``repro.obs.trace``, kept as ``rec["program"]``; see
``chipbench/program_spans.py``).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import hashlib
import importlib.util
import inspect
import json
import os
import pickle
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)
CACHE = os.path.join(PKG, "cache")
# Sessions still waiting for their first token this long after the window
# closes count as unanswered.
TAIL_S = 60.0
# Projections encoded at once on first runs: each holds an f32 copy and a
# sort on the device and ~4 GB of numpy temporaries on the host.
REFORMAT_WORKERS = 2


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _read_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict               # configs/<config>.json
    traffic: Dict              # traffic/<traffic>.json
    params: Dict               # cells/<cell>.json
    per_layer: List[Dict]      # BENCHMARK.json per_layer entries
    end_to_end: List[Dict]
    reference: Any             # the configuration's reference module

    @property
    def model(self) -> Dict:
        """The model dict the reference and the FLOP count read."""
        return dict(self.config["model"], sparsity=self.config["sparsity"])


def load_cell(name: str, root: str = ROOT, *, bench: Optional[Dict] = None,
              config: Optional[Dict] = None, traffic: Optional[Dict] = None,
              params: Optional[Dict] = None) -> Cell:
    """Find a cell and its files by name. Tests pass ``bench``/``config``/
    ``traffic``/``params`` to stand in for the files."""
    bench = bench or _read_json(root, "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    pkg = os.path.join(root, "chipbench")
    config = config or _read_json(pkg, "configs", f"{w['config']}.json")
    return Cell(
        name=name, workload=w, config=config,
        reference=load_reference(config.get("reference", "reference"), root),
        traffic=traffic or _read_json(pkg, "traffic", f"{w['traffic']}.json"),
        params=params or _read_json(pkg, "cells", f"{name}.json"),
        per_layer=[m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])],
        end_to_end=[m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])])


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT) -> Callable:
    return _load(os.path.join(root, "chipbench", "metrics", f"{metric}.py"),
                 f"chipbench_metric_{metric.replace('.', '_')}").read


@functools.lru_cache(maxsize=None)
def load_reference(module: str, root: str = ROOT):
    """``chipbench/<module>.py`` under ``root``, loaded once a process so
    that every cell of a configuration reuses its compiled pieces."""
    if not module.isidentifier():
        raise ValueError(f"reference {module!r} is not a module name")
    return _load(os.path.join(root, "chipbench", f"{module}.py"),
                 f"chipbench_reference_{module}")


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it), else a fixed directory inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CACHE, "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# weights: seeded dense draw -> the program's reformat tool -> cached
# ---------------------------------------------------------------------------

def model_config(cell: Cell):
    from repro.models.config import ModelConfig
    return ModelConfig(name=cell.workload["config"], **cell.config["model"])


# The layer of a leaf of a scanned stack: its leading axis is the layer.
SCANNED = "scanned"


def _part(k):
    """A path entry's dict key, list index or attribute name."""
    for field in ("key", "idx", "name"):
        if hasattr(k, field):
            return getattr(k, field)
    raise TypeError(f"unknown path entry {k!r}")


def _leaf_name(path) -> tuple:
    """(name, layer): the leaf's name as ``weights`` and the reference name
    it, and its layer -- None outside the layers, ``SCANNED`` in a scanned
    stack, else its index in the list of layers."""
    keys = [_part(k) for k in path]
    if keys[0] != "layers":
        return ".".join(keys), None
    if isinstance(keys[1], int):
        return ".".join(keys[2:]), keys[1]
    return ".".join(keys[1:]), SCANNED


def _param_shapes(cfg):
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    return jax.eval_shape(lambda: transformer.init_model(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))


def check_layout(cell: Cell, shapes) -> None:
    """The program's parameter layout must be the one the reference draws:
    the same leaf names and shapes in every layer, or the two would compute
    different models."""
    import jax
    want = cell.reference.layer_shapes(cell.model)
    if isinstance(want, dict):
        want = [want] * cell.model["n_layers"]
    want = [{k: tuple(v) for k, v in layer.items()} for layer in want]
    have: Dict[int, Dict[str, tuple]] = {}
    for path, sd in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name, layer = _leaf_name(path)
        if layer == SCANNED:
            for i in range(sd.shape[0]):
                have.setdefault(i, {})[name] = tuple(sd.shape[1:])
        elif layer is not None:
            have.setdefault(layer, {})[name] = tuple(sd.shape)
    if len(have) != len(want):
        raise RuntimeError(f"program has {len(have)} layers, the reference "
                           f"{len(want)}")
    for i, layer in enumerate(want):
        if have[i] != layer:
            raise RuntimeError(
                f"program layer {i} layout {sorted(have[i].items())} is "
                f"not the reference's {sorted(layer.items())}")


def dense_params(cell: Cell, shapes):
    """Every leaf drawn on the device in one jitted call, in bf16, from
    (weight seed, name, layer): by ``weights``, else by the reference's
    ``leaf``."""
    import jax
    import jax.numpy as jnp
    from chipbench import weights
    seed = cell.config["weight_seed"]
    ref = cell.reference
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(name, layer, shape, dtype):
        try:
            return weights.leaf(seed, name, layer, shape, dtype)
        except ValueError:
            if not hasattr(ref, "leaf"):
                raise
        return ref.leaf(seed, name, layer, shape, dtype)

    def build():
        leaves = []
        for path, sd in flat:
            name, layer = _leaf_name(path)
            if layer == SCANNED:
                leaves.append(jnp.stack([
                    draw(name, i, sd.shape[1:], sd.dtype)
                    for i in range(sd.shape[0])]))
            else:
                leaves.append(draw(name, layer or 0, sd.shape, sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)()


def _nest(path, leaf):
    for k in reversed(path):
        leaf = {_part(k): leaf}
    return leaf


def _dig(tree, path):
    for k in path:
        tree = tree[_part(k)]
    return tree


def reformat(cell: Cell, box: List):
    """The program's reformat tool (``pruning.sparsify_params`` with
    ``launch.serve.should_sparsify``, then ``group_projections``), run over
    the projection leaves a few at a time. ``box`` holds the dense tree and
    is emptied, so each dense leaf is freed once it is encoded. Returns
    (params, timings)."""
    import jax
    from repro.core import pruning
    from repro.launch import serve
    sparsity = cell.config["sparsity"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(box.pop())
    out = [leaf for _, leaf in flat]

    def one(i, path, leaf):
        res = pruning.sparsify_params(_nest(path, leaf), sparsity,
                                      should_sparsify=serve.should_sparsify)
        return i, _dig(res, path)

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(REFORMAT_WORKERS) as ex:
        jobs = [ex.submit(one, i, path, leaf)
                for i, (path, leaf) in enumerate(flat)
                if serve.should_sparsify(jax.tree_util.keystr(path))]
        flat = None
        for job in concurrent.futures.as_completed(jobs):
            i, val = job.result()
            out[i] = val
    params = pruning.group_projections(
        jax.tree_util.tree_unflatten(treedef, out))
    jax.block_until_ready(params)
    return params, {"reformat_wall_s": time.perf_counter() - t0,
                    "workers": REFORMAT_WORKERS}


def _sources_digest() -> str:
    from repro.core import pruning, sparse_linear, tiled_csl
    from repro.launch import serve
    from chipbench import weights
    h = hashlib.sha256()
    for mod in (tiled_csl, pruning, sparse_linear, serve, weights):
        h.update(inspect.getsource(mod).encode())
    return h.hexdigest()


def params_key(cell: Cell, shapes) -> str:
    import jax
    h = hashlib.sha256()
    h.update(json.dumps({k: cell.config[k] for k in
                         ("model", "sparsity", "weight_seed")},
                        sort_keys=True).encode())
    h.update(str(jax.tree_util.tree_structure(shapes)).encode())
    h.update(str([tuple(x.shape) for x in jax.tree.leaves(shapes)]).encode())
    h.update(_sources_digest().encode())
    if hasattr(cell.reference, "leaf"):
        with open(cell.reference.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def served_params(cell: Cell, cfg, setup: Dict):
    """The served (Tiled-CSL) parameters: loaded from the checkpoint under
    ``chipbench/cache/params`` when its key matches, else drawn, reformatted
    by the program's tool and saved there."""
    import jax
    shapes = _param_shapes(cfg)
    check_layout(cell, shapes)
    key = params_key(cell, shapes)
    name = cell.workload["config"]
    directory = os.path.join(CACHE, "params")
    path = os.path.join(directory, f"{name}-{key}.pkl")
    t0 = time.perf_counter()
    if os.path.exists(path):
        with open(path, "rb") as f:
            host = pickle.load(f)
        t1 = time.perf_counter()
        params = jax.device_put(host)
        jax.block_until_ready(params)
        del host
        setup.update(checkpoint="hit", load_s=t1 - t0,
                     to_device_s=time.perf_counter() - t1)
        return params
    box = [dense_params(cell, shapes)]
    jax.block_until_ready(box)
    setup["draw_s"] = time.perf_counter() - t0
    params, timing = reformat(cell, box)
    setup.update(timing)
    t1 = time.perf_counter()
    os.makedirs(directory, exist_ok=True)
    for old in os.listdir(directory):
        if old.startswith(f"{name}-"):
            os.remove(os.path.join(directory, old))
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(jax.device_get(params), f, protocol=5)
    os.replace(tmp, path)
    setup.update(checkpoint="written", save_s=time.perf_counter() - t1)
    return params


def tree_bytes(params) -> int:
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# serving on the wall clock
# ---------------------------------------------------------------------------

def make_server(params, cfg, cell: Cell):
    from repro.serving import api
    from repro.serving.config import SchedulerConfig, ServeConfig
    p = cell.params
    config = ServeConfig(
        scheduler=SchedulerConfig(n_slots=p["n_slots"], max_len=p["max_len"]),
        cache_kind="paged", block_size=p["block_size"],
        n_blocks=p["n_blocks"], backend=p["backend"]).validate()
    return api.StreamingServer(params, cfg, config=config)


def warm_shapes(server, cell: Cell, vocab: int) -> int:
    """Compile (or load from the cache) every prefill bucket this cell's
    traffic can reach -- from its shortest prompt's bucket up to max_len,
    which a preempted request's resume can reach -- and the decode step,
    by serving one two-token request per bucket."""
    from repro.serving import api
    max_len = cell.params["max_len"]
    lo = cell.traffic["prompt_len"]["lo"]
    buckets = [b for b in server.batcher.buckets if b >= lo]
    buckets = [b for b in server.batcher.buckets if b >= min(buckets)]
    rng = np.random.default_rng(0)
    for b in buckets:
        n = min(b, max_len - 1)
        server.submit(api.GenerationRequest(
            prompt=rng.integers(0, vocab, n).astype(np.int32),
            max_new_tokens=2))
    server.run_until_drained()
    return len(buckets)


@dataclasses.dataclass
class Served:
    """One request as the harness saw it."""
    due: float
    prompt: np.ndarray
    max_new: int
    phase: str
    sid: str = ""
    submit_t: float = -1.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish_reason: str = ""
    rejected: str = ""


def counters(metrics) -> Dict[str, float]:
    """Every numeric field of the program's ``SchedulerMetrics``."""
    out = {}
    for f in dataclasses.fields(metrics):
        v = getattr(metrics, f.name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f.name] = float(v)
    return out


def serve(server, reqs: List[Served], *, open_at: float, seconds: float,
          trace_dir: Optional[str] = None,
          tail_s: Optional[float] = None) -> Dict[str, Any]:
    """Drive ``server`` open-loop: each request is submitted when it is due
    (schedule times are seconds after this call), ``server.step()`` runs
    whenever anything is queued or decoding. After the window closes it
    goes on until every request due in the window has its first token, at
    most ``tail_s`` (default ``TAIL_S``). Returns the window's log.

    With ``trace_dir`` the window runs under the JAX profiler, and the
    program's tracer (``server.batcher.tracer``) and its compile listener
    are on from the window's open to its close; the log then holds
    ``program``, the record ``chipbench/program_spans.py`` reads."""
    tail_s = TAIL_S if tail_s is None else tail_s
    import jax
    from repro.obs.trace import record_compiles
    from repro.serving import api
    from chipbench import program_spans
    by_sid: Dict[str, Served] = {}

    def on_token(ev):
        r = by_sid[ev.session_id]
        r.times.append(time.perf_counter())
        r.tokens.append(int(ev.token))
        if ev.finish_reason:
            r.finish_reason = ev.finish_reason

    origin = time.perf_counter()
    t_open, t_close = origin + open_at, origin + open_at + seconds
    window = [r for r in reqs if r.phase == "window"]
    steps: List[tuple] = []
    lateness: List[float] = []
    snap: Dict[str, Dict] = {}
    queue: Dict[str, float] = {}
    tracer = server.batcher.tracer
    program = ann = stop_compiles = None
    i, n = 0, len(reqs)
    while True:
        now = time.perf_counter()
        if "open" not in snap and now >= t_open:
            if trace_dir is not None:
                tracer.clear()
                stop_compiles = record_compiles(tracer.enable())
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                ann = jax.profiler.TraceAnnotation("chipbench.window")
                ann.__enter__()
                # the two clocks are set against each other at both ends
                prog_open = tracer.clock()
            now = time.perf_counter()
            snap["open"] = {"t": now, "wall": time.time(),
                            "counters": counters(server.metrics)}
            queue["open"] = server.queue_depth
        if "close" not in snap and now >= t_close:
            snap["close"] = {"t": now, "counters": counters(server.metrics)}
            queue["close"] = server.queue_depth
            if ann is not None:
                prog_close = tracer.clock()
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                ann = None
                tracer.disable()
                stop_compiles()
                program = program_spans.program_record(tracer, prog_open,
                                                       prog_close)
        if "close" in snap and (all(r.times or r.rejected for r in window)
                                or now > t_close + tail_s):
            break
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            while i < n and origin + reqs[i].due <= now:
                r = reqs[i]
                r.submit_t = time.perf_counter()
                lateness.append(r.submit_t - (origin + r.due))
                try:
                    r.sid = server.submit(api.GenerationRequest(
                        prompt=r.prompt, max_new_tokens=r.max_new,
                        on_token=on_token))
                    by_sid[r.sid] = r
                except (api.Backpressure, api.RequestRejected) as e:
                    r.rejected = type(e).__name__
                i += 1
        if server.busy:
            before = server.metrics.decode_tokens
            with jax.profiler.TraceAnnotation("chipbench.step"):
                t0 = time.perf_counter()
                server.step()
                t1 = time.perf_counter()
            steps.append((t0, t1, server.metrics.decode_tokens > before))
        else:
            nxt = origin + reqs[i].due if i < n else now + 1e-3
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.01))
    out = {"origin": origin, "open": snap["open"], "close": snap["close"],
           "steps": steps, "lateness": lateness, "queue": queue,
           "requests": reqs, "end_t": time.perf_counter()}
    if program is not None:
        out["program"] = program
    return out


# ---------------------------------------------------------------------------
# reading the window
# ---------------------------------------------------------------------------

def window_record(cell: Cell, run: Dict) -> Dict[str, Any]:
    """Everything the metrics read, from one served window."""
    from chipbench import flops
    m, ref = cell.model, cell.reference
    t0, t1 = run["open"]["t"], run["close"]["t"]
    window_s = t1 - t0
    reqs: List[Served] = run["requests"]
    due = [r for r in reqs if r.phase == "window"]
    ttft, itl = [], []
    for r in due:
        # a request never answered waited until the run gave up on it
        first = r.times[0] if r.times else run["end_t"]
        ttft.append(first - (run["origin"] + r.due))
    n_tokens = 0
    req_flops = 0.0
    for r in reqs:
        ts = r.times
        for j, t in enumerate(ts):
            if not t0 <= t < t1:
                continue
            n_tokens += 1
            if j == 0:
                req_flops += flops.prefill_flops(m, len(r.prompt), ref)
            else:
                itl.append(t - ts[j - 1])
                req_flops += flops.decode_flops(m, len(r.prompt) + j, ref)
    c0, c1 = run["open"]["counters"], run["close"]["counters"]
    delta = {k: c1[k] - c0[k] for k in c1}
    inside = [(b - a, dec) for a, b, dec in run["steps"] if t0 <= a < t1]
    host_step = sum(d for d, _ in inside)
    rec = {"window_s": window_s, "tokens": n_tokens, "ttft_s": ttft,
           "itl_s": itl, "delta": delta, "host_step_s": host_step,
           "steps_in_window": len(inside),
           "step_s": [d for d, _ in inside],
           "decode_launches": sum(1 for _, dec in inside if dec),
           "required_flops": req_flops,
           "lateness_s": run["lateness"], "queue": run["queue"],
           "counters": {"open": c0, "close": c1}}
    if "program" in run:
        rec["program"] = run["program"]
    return rec


def pctl(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(rec: Dict, setup_s: float, peak_bytes: int) -> Dict[str, float]:
    return {"ttft_p50_ms": pctl(rec["ttft_s"], 50) * 1e3,
            "itl_p50_ms": pctl(rec["itl_s"], 50) * 1e3,
            "hbm_peak_gib": peak_bytes / 2 ** 30,
            "setup_s": setup_s}


# ---------------------------------------------------------------------------
# correctness: a seeded sample of finished requests against the reference
# ---------------------------------------------------------------------------

def sample_finished(run: Dict, k: int, seed: int) -> List[Served]:
    """``k`` requests due in the window and finished by its close, drawn
    from the seed, always with the longest among them."""
    t1 = run["close"]["t"]
    done = [r for r in run["requests"] if r.phase == "window" and r.times
            and len(r.times) == r.max_new and r.times[-1] <= t1]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.max_new, r.due))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(abs(int(seed)) + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def unanswered(run: Dict) -> int:
    """Requests due in the window that were refused, failed, or never
    produced a first token."""
    bad = 0
    for r in run["requests"]:
        if r.phase != "window":
            continue
        if r.rejected or not r.times or (
                r.finish_reason and r.finish_reason != "max_new_tokens"):
            bad += 1
    return bad


def check(cell: Cell, sample: List[Served], *, control: bool = False
          ) -> Dict[str, Any]:
    if not sample:
        return {"rows": [], "gap": None, "tokens": 0}
    rows = cell.reference.gaps(cell.model, cell.config["weight_seed"],
                               [r.prompt for r in sample],
                               [r.tokens for r in sample], control=control)
    out = {"rows": rows, "gap": max(r["gap"] for r in rows),
           "tokens": sum(r["tokens"] for r in rows),
           "agree": sum(r["agree"] for r in rows)}
    if control:
        out["control_gap"] = max(r["control_gap"] for r in rows)
    return out


def build_requests(cell: Cell, seed: int, seconds: float, vocab: int,
                   rate: Optional[float] = None) -> List[Served]:
    from chipbench import traffic
    rate = rate or cell.params["rate_per_s"]
    phases = [("warmup", cell.params["warmup_s"]), ("window", seconds),
              ("tail", TAIL_S)]
    return [Served(r.due, r.prompt, r.max_new, r.phase)
            for r in traffic.schedule(cell.traffic, rate=rate, seed=seed,
                                      vocab=vocab, phases=phases)]



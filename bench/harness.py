"""One run of one benchmark cell: set-up, measured window, correctness.

A cell is a model configuration (``bench/configs/<config>.json``) under a
traffic mix (``bench/traffic/<traffic>.json``: the training job's nodes,
mesh, rows per node and exchange settings), named in ``BENCHMARK.json``;
its correctness limits sit in ``bench/workloads/<cell>.json``.  Per-layer
metrics are readers in ``bench/metrics/<metric>.py``.  A configuration
names its plain reference module (``bench/<reference>.py``, default
``bench/reference.py``), which also checks the file against the program
and counts the model's operations; a cell of several nodes trains that
reference on each node and mixes the nodes as the program's exchange
states (``bench/ring.py``).  Nothing here names a cell, a configuration,
an architecture, a kernel or a metric: a new one is a new file.

The timed path is the program's normal one:
``build_train_setup`` -> ``init_train_state(setup, seed)`` -> the compiled
``setup.train_step``, fed by ``SyntheticLMDataset.global_batch_arrays`` and
``jax.device_put(..., setup.batch_sharding)``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import bench
from bench import correct as C
from bench import counts

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_STEPS = 3          # steps driven through the window's call and compared
#: the control's precision: one below the configurations' float32
CONTROL = "bf16"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(name: str) -> dict:
    """The cell's entry of BENCHMARK.json and the files it names."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return {
        "cell": cell,
        "config": load_json(ROOT / confs[cell["config"]]["file"]),
        "traffic": traffic,
        "limits": load_json(BENCH / "workloads" / f"{name}.json")["limits"],
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def require_chip(chips: int) -> dict:
    """The accelerator the cell runs on; exits where there is none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return device_info(chips)


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts the compilations JAX reports while ``active``."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.count = 0

        def on_duration(event, duration, **kw):
            if self.active and event.endswith("backend_compile_duration"):
                self.count += 1

        mon.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def reference_module(conf: dict):
    """The configuration's plain reference module: ``bench/<name>.py`` for
    its ``reference`` key, ``bench/reference.py`` without one."""
    name = conf.get("reference", "reference")
    return importlib.import_module(f"bench.{name}")


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked against
    the file's published keys by its reference module."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]),
                              **conf.get("program", {}))
    bad = reference_module(conf).check_program(conf, cfg)
    if bad:
        raise SystemExit(f"bench: {conf['arch']} as the program builds it "
                         f"differs from its file: {bad}")
    return cfg


@dataclasses.dataclass
class System:
    """The program's train step for one cell, and what feeds it."""
    setup: object
    dataset: object
    step: object          # what the window calls: the compiled train step
    compiled: object      # the compiled train step itself
    names: list           # leaf names of the parameter tree, in order
    tokens_per_step: int
    nodes: int = 1
    #: per leaf, the logical shape and the dimension on which a leaf of
    #: the train state stacks its nodes' replicas
    stacking: list = dataclasses.field(default_factory=list)

    def by_node(self, leaves: list) -> dict:
        """The leaves of a parameter-shaped tree as host arrays by name:
        ``<leaf>`` on one node, ``node<i>.<leaf>`` for each node of a
        ring."""
        if self.nodes == 1:
            return {n: np.asarray(a) for n, a in zip(self.names, leaves)}
        out = {}
        for name, (shape, dim), a in zip(self.names, self.stacking, leaves):
            for i, part in enumerate(np.split(np.asarray(a), self.nodes,
                                              axis=dim)):
                out[f"node{i}.{name}"] = part[tuple(slice(0, n)
                                                    for n in shape)]
        return out

    def feed(self, k: int, keep: list | None = None):
        import jax
        rows = self.dataset.global_batch_arrays(k)
        if keep is not None:
            keep.append(rows)
        return jax.device_put(rows, self.setup.batch_sharding)


def leaf_name(path) -> str:
    return ".".join(str(p.key) for p in path if hasattr(p, "key"))


def build_system(files: dict, seed: int, step_wrapper=None) -> tuple:
    """Set up the cell's train step and its state from ``seed``."""
    import jax
    from repro.data import SyntheticLMDataset
    from repro.launch import train as LT
    from repro.launch.mesh import make_cpu_mesh
    from repro.models.params import ParamDef

    conf, tr = files["config"], files["traffic"]
    cfg = model_config(conf)
    opt, ex = conf["optimizer"], tr["exchange"]
    nodes = tr["nodes"]
    batch = nodes * tr["seqs_per_node"]
    mesh = make_cpu_mesh(data=tr["mesh"]["data"], model=tr["mesh"]["model"])
    setup = LT.build_train_setup(
        cfg, mesh, consensus_nodes=nodes, algorithm=ex["algorithm"],
        gamma=ex["gamma"], quant_mode=ex["quant_mode"],
        fixed_step0=ex["fixed_step0"], optimizer=opt["name"],
        schedule=opt["schedule"], lr=opt["lr"], use_pallas=ex["use_pallas"],
        global_batch=batch, seq_len=tr["seq_len"],
        wire_packing=ex["wire_packing"], wire_codec=ex["wire_codec"],
        # the program bakes its noise seed into the step: a ring states
        # one, so that one compiled step serves every --seed; one node
        # draws no noise
        seed=ex.get("noise_seed", seed))
    o = setup.optimizer
    stated = (opt["b1"], opt["b2"], opt["eps"], ex["self_weight"])
    built = (o.b1, o.b2, o.eps, setup.consensus.cfg.self_weight)
    if stated != built:
        raise SystemExit(f"bench: optimizer/exchange as built {built} "
                         f"differ from the files {stated}")
    state = LT.init_train_state(setup, seed)
    ds = SyntheticLMDataset(cfg.vocab_size, tr["seq_len"], batch, seed=seed,
                            n_shards=setup.ctx.dp)
    paths = jax.tree_util.tree_flatten_with_path(state["params"])[0]
    defs = jax.tree.leaves(setup.defs.storage,
                           is_leaf=lambda x: isinstance(x, ParamDef))
    sys_ = System(setup=setup, dataset=ds, step=None, compiled=None,
                  names=[leaf_name(p) for p, _ in paths],
                  tokens_per_step=batch * tr["seq_len"], nodes=nodes,
                  stacking=[(d.shape, d.fsdp_dim) for d in defs])
    example = {k: jax.ShapeDtypeStruct((batch, tr["seq_len"]), np.int32,
                                       sharding=sh)
               for k, sh in setup.batch_sharding.items()}
    compiled = setup.train_step.lower(state, example).compile()
    sys_.compiled = compiled
    sys_.step = compiled if step_wrapper is None else step_wrapper(
        compiled, sys_)
    return sys_, state


def drive_check_steps(sys_: System, state) -> tuple:
    """The first CHECK_STEPS steps through the window's own call and feed,
    with what the comparison needs copied out on the way: the loss of each
    step, the first gradient, the change of the parameters, and the rows
    fed."""
    import jax
    params0 = jax.tree.leaves(jax.device_get(state["params"]))
    rows, losses = [], []
    if "m" not in state["opt"]:
        raise SystemExit("bench: the comparison reads the first gradient "
                         "from Adam's first moment; the optimizer has none")
    for k in range(CHECK_STEPS):
        state, metrics = sys_.step(state, sys_.feed(k, keep=rows))
        # the mean over the nodes
        losses.append(float(metrics["loss"]))
        if k == 0:
            # Adam's first moment after one step is (1 - b1) * g
            b1 = np.float32(1.0) - np.float32(sys_.setup.optimizer.b1)
            grad1 = {n: m / b1 for n, m in sys_.by_node(jax.tree.leaves(
                jax.device_get(state["opt"]["m"]))).items()}
    jax.block_until_ready(state)
    params0 = sys_.by_node(params0)
    params3 = sys_.by_node(jax.tree.leaves(jax.device_get(state["params"])))
    prog = {"loss": losses, "grad1": grad1,
            "change": {n: params3[n] - a for n, a in params0.items()}}
    return state, prog, [(r["tokens"], r["labels"]) for r in rows], metrics


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

def run_window(sys_: System, state, seconds: float, counter: CompileCounter,
               trace_dir: str | None = None) -> dict:
    """Train until ``seconds`` have passed, one step in flight: the host
    builds step k+1's rows while the device runs step k, and reads each
    step's loss one step late.  ``phases`` holds, per turn of the loop,
    the host's seconds in building the rows, dispatching the step and
    waiting for the previous step's loss; ``gc`` the garbage collections
    inside the window, as (generation, seconds)."""
    import jax
    from jax.profiler import TraceAnnotation
    k = CHECK_STEPS
    losses, phases, collections, pending = [], [], [], None
    started = {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            collections.append((info["generation"],
                                time.perf_counter() - started.pop("t")))

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    counter.active = True
    gc.callbacks.append(on_gc)
    t0 = time.perf_counter()
    with TraceAnnotation("window"):
        while True:
            ta = time.perf_counter()
            with TraceAnnotation("input"):
                batch = sys_.feed(k)
            tb = time.perf_counter()
            with TraceAnnotation("dispatch"):
                state, metrics = sys_.step(state, batch)
            tc = time.perf_counter()
            k += 1
            if pending is not None:
                with TraceAnnotation("readback"):
                    losses.append(float(pending["loss"]))
            td = time.perf_counter()
            phases.append((tb - ta, tc - tb, td - tc))
            pending = metrics
            if td - t0 >= seconds:
                break
        with TraceAnnotation("readback"):
            losses.append(float(pending["loss"]))
    t1 = time.perf_counter()
    gc.callbacks.remove(on_gc)
    counter.active = False
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {"state": state, "steps": k - CHECK_STEPS, "window_s": t1 - t0,
            "losses": losses, "phases": phases, "gc": collections,
            "outputs": {kk: float(v) for kk, v in pending.items()}}


def print_turns(label: str, win: dict) -> None:
    """The loop's longest turn, split into its phases, and the garbage
    collections, on standard error."""
    turns = np.asarray(win["phases"])
    worst = int(turns.sum(axis=1).argmax())
    gen2 = [t for g, t in win["gc"] if g == 2]
    print(f"bench: {label}: {len(turns)} turns, median "
          f"{np.median(turns.sum(1)):.4f} s; longest {turns[worst].sum():.4f}"
          f" s (turn {worst}: input {turns[worst][0]:.4f}, dispatch "
          f"{turns[worst][1]:.4f}, readback {turns[worst][2]:.4f}); gc "
          f"{len(win['gc'])} collections, "
          f"{sum(t for _, t in win['gc']):.4f} s, generation 2: "
          f"{[round(t, 4) for t in gen2]}", file=sys.stderr, flush=True)


def memory(sys_: System, devices) -> dict:
    """The fullest chip's memory: the runtime's ``peak_bytes_in_use`` and
    the compiled step's own total per device (arguments + outputs -
    aliased + temporaries).  On a TPU v5e the runtime's peak leaves the
    step's temporaries out, so the peak is the larger of the two."""
    ma = sys_.compiled.memory_analysis()
    compiled = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices] if devices[0].platform != "cpu" else [0]
    return {"peak_bytes": max(max(peaks), compiled),
            "peak_bytes_in_use": max(peaks), "compiled_bytes": compiled,
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def reference(files: dict, seed: int, rows: list, precision: str = "f32",
              exchange: bool = True):
    """The configuration's plain reference trained on ``rows`` from
    ``seed``: one node, or each node of a ring on its own rows of each
    step, mixed as the traffic's exchange states (without ``exchange``,
    each node alone)."""
    conf, tr = files["config"], files["traffic"]
    R = reference_module(conf)
    if tr["nodes"] == 1:
        return R.run_reference(R.Model.from_config(conf), conf["optimizer"],
                               seed, rows, precision)
    from bench import ring
    return ring.run_ring(R, conf, seed, ring.node_rows(rows, tr["nodes"]),
                         tr, precision, exchange)


def kernel_counts(files: dict) -> dict:
    """Per kernel, the flops and bytes one call needs on one chip of the
    cell (bench/counts.py), with the reference module's own kernels."""
    conf, tr = files["config"], files["traffic"]
    R = reference_module(conf)
    # the wire kernels' rows, on a ring
    sizes = [math.prod(shape) for _, shape, _ in R.leaf_specs(
        R.Model.from_config(conf))] if tr["nodes"] > 1 else []
    out = counts.kernel_counts(conf, tr, sizes)
    if hasattr(R, "kernel_counts"):
        out.update(R.kernel_counts(conf, tr))
    return out


def run_cell(files: dict, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, step_wrapper=None) -> dict:
    """Set-up, window and correctness of one cell; returns the result line
    and the numbers compared."""
    import jax
    from bench import scopes as SC
    if trace:
        # scope names live in the HLO's metadata, which the compilation
        # cache's key leaves out by default: an executable of the same ops
        # under other scopes would be served, and its text read
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    counter = CompileCounter()
    sys_, state = build_system(files, seed, step_wrapper)
    state, prog, rows, _ = drive_check_steps(sys_, state)
    setup_s = time.perf_counter() - t_start
    print(f"bench: set-up {setup_s:.3f} s", file=sys.stderr, flush=True)

    chips = device["count"]
    devices = jax.devices()[:chips]
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    win = run_window(sys_, state, seconds, counter,
                     tmp.name if tmp else None)
    print(f"bench: compilations inside the window: {counter.count}",
          file=sys.stderr, flush=True)
    print_turns("window", win)
    mem = memory(sys_, devices)
    tokens_per_s_per_chip = (win["steps"] * sys_.tokens_per_step
                             / win["window_s"] / chips)
    failed = sum(not math.isfinite(v) for v in prog["loss"] + win["losses"])
    attempted = CHECK_STEPS + win["steps"]
    obs = {"steps": win["steps"], "window_s": win["window_s"],
           "tokens_per_step": sys_.tokens_per_step, "chips": chips,
           "tokens_per_s_per_chip": tokens_per_s_per_chip,
           "outputs": win["outputs"],
           "flops_per_token": reference_module(files["config"])
           .flops_per_token(files["config"], files["traffic"]["seq_len"]),
           "kernel_counts": kernel_counts(files),
           "peaks": peaks_for(device["kind"]) if device["platform"] == "tpu"
           else None}
    reduced = None
    if tmp is not None:
        reduced = SC.reduce(SC.load(tmp.name),
                            SC.op_names(sys_.compiled.as_text()), chips)
        tmp.cleanup()
        obs["trace"] = reduced
    out_state = win.pop("state")
    del state, out_state, win
    sys_.step = sys_.compiled = None
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference(files, seed, rows)
    ref_s = time.perf_counter() - t_ref
    numbers, _ = C.compare(prog, ref, files["limits"])
    ok = failed == 0 and C.passes(numbers)
    print(f"bench: reference {ref_s:.3f} s", file=sys.stderr, flush=True)

    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": dict(device)}
    result["device"]["memory_peak_bytes"] = mem["peak_bytes"]
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        for m in files["per_layer"]:
            v = read_metric(m["name"], obs)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = reduced["breakdown"]
    else:
        e2e = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
               "peak_hbm_gib": mem["peak_bytes"] / 2 ** 30,
               "setup_s": setup_s}
        for m in files["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in numbers}
    print(f"bench: window {obs['window_s']:.3f} s, {obs['steps']} steps, "
          f"tokens/s/chip {tokens_per_s_per_chip:.1f}, memory {mem}",
          file=sys.stderr, flush=True)
    return result


def read_metric(name: str, obs: dict):
    """The reader ``metrics/<name>.py`` of the ``bench`` package's first
    directory that holds one, applied to ``obs``."""
    path = next(p for p in (Path(d) / "metrics" / f"{name}.py"
                            for d in bench.__path__) if p.exists())
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


# ---------------------------------------------------------------------------
# readings for the limits: the program on many seeds, and the control
# ---------------------------------------------------------------------------

def _halve(rows: list[tuple], nodes: int = 1) -> list[tuple]:
    """The second half of each node's rows of each step replaced by its
    first: half of the batch left out, the mean taken over the rest."""
    def half(a):
        a = a.copy()
        for part in np.split(a, nodes):
            b = len(part) // 2
            part[b:2 * b] = part[:b]
        return a
    return [(half(t), half(l)) for t, l in rows]


def readings(files: dict, seeds: list[int], stand_in: str | None = None,
             step_wrapper=None, out_path: str | None = None) -> list[dict]:
    """The numbers compared, without a window, per seed: the program's
    first steps against the reference, or (``stand_in``) the reference
    computed in the precision below the configuration's (``control``), or
    with half of each node's rows left out (``half_batch``) or with the
    exchange between the nodes left out (``no_exchange``), in the
    program's place.  One process, so the compiled programs are reused.
    Each seed's record, with the per-leaf norms the numbers are read from,
    is printed and appended to ``out_path``."""
    from repro.data import SyntheticLMDataset
    tr = files["traffic"]
    out = []
    for seed in seeds:
        t = time.perf_counter()
        if stand_in is None:
            sys_, state = build_system(files, seed, step_wrapper)
            state, prog, rows, _ = drive_check_steps(sys_, state)
            del state, sys_
            gc.collect()
        else:
            ds = SyntheticLMDataset(files["config"]["vocab_size"],
                                    tr["seq_len"],
                                    tr["nodes"] * tr["seqs_per_node"],
                                    seed=seed, n_shards=tr["mesh"]["data"])
            rows = [(r["tokens"], r["labels"]) for r in
                    (ds.global_batch_arrays(k) for k in range(CHECK_STEPS))]
            fed = rows
            if stand_in == "half_batch":
                fed = _halve(rows, tr["nodes"])
            prog = reference(files, seed, fed,
                             CONTROL if stand_in == "control" else "f32",
                             exchange=stand_in != "no_exchange")
        ref = reference(files, seed, rows)
        numbers, table = C.compare(prog, ref, files["limits"])
        rec = {"seed": seed, "stand_in": stand_in,
               "seconds": time.perf_counter() - t,
               "numbers": {n: v for n, v, _ in numbers},
               "loss": [prog["loss"], ref["loss"]]}
        print(json.dumps(rec), flush=True)
        if out_path:
            with open(out_path, "a") as f:
                f.write(json.dumps(dict(rec, leaves=table)) + "\n")
        out.append(rec)
    return out

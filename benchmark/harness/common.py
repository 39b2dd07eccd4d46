"""What every runner shares: the run's context, spans, lines of output."""

from __future__ import annotations

import contextlib
import json
import os
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


#: where cells, configurations, traffic mixes and metric readers are looked
#: up by name; a test appends an overlay directory to show that a later PR
#: needs to add files only
SEARCH = [BENCH]


def find(*parts) -> str:
    for d in reversed(SEARCH):
        path = os.path.join(d, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(os.path.join(*parts) + f" not under {SEARCH}")


def load_json(*parts):
    with open(find(*parts)) as f:
        return json.load(f)


class Run:
    """One run of one cell: its files, its devices, what it recorded."""

    def __init__(self, cell_name, seed, seconds, trace, t_start):
        self.cell_name, self.seed = cell_name, int(seed)
        self.seconds, self.trace_on = float(seconds), bool(trace)
        self.t_start = t_start          # perf_counter at process start
        self.cell = load_json("workloads", cell_name + ".json")
        self.config = load_json("configs", self.cell["config"] + ".json")
        self.traffic = load_json("traffic", self.cell["traffic"] + ".json")
        self.devices = []
        self.peaks = {}
        self.dev_tag = "?"
        self.spans = {}                 # name -> [(t0, t1)] host clock
        self.counters = {}              # what the runner counted
        self.end_to_end = {}            # name -> value
        self.exe_bytes = {}             # program -> compiler's byte counts
        self.trace = None               # reduced trace (harness/trace.py)
        self.trace_host = None          # (t0, t1) host clock of the trace
        self.setup_split = {}
        self.reference_s = 0.0          # time in the check; not set-up
        self.failures = []              # reasons this run counts as failed
        self.attempted = self.failed = 0
        self.correct = None
        self.compared = []              # (what, value, limit, ok)
        self.with_control = False       # benchmark/control.py sets it
        self.control_correct = None
        self.control_compared = []

    def say(self, msg: str):
        print(f"[{self.dev_tag}] {msg}", flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append((t0, time.perf_counter()))

    @contextlib.contextmanager
    def phase(self, name: str):
        """A part of set-up, timed for the split printed before the result."""
        t0 = time.perf_counter()
        yield
        self.setup_split[name] = round(
            self.setup_split.get(name, 0.0) + time.perf_counter() - t0, 3)

    def fail_run(self, why: str):
        self.failures.append(why)
        self.say(f"RUN COUNTS AS FAILED: {why}")

    def compare(self, what: str, value: float, limit: float) -> bool:
        ok = bool(value <= limit)
        self.compared.append((what, float(value), float(limit), ok))
        self.say(f"check: {what} = {value:.6g}  limit {limit:.6g}  "
                 f"{'ok' if ok else 'NOT OK'}")
        return ok


def trace_dir(run: Run) -> str:
    return os.path.join(ROOT, ".bench_out", "trace-" + run.cell_name)


def start_trace(run: Run):
    import shutil

    import jax

    d = trace_dir(run)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    run._trace_t0 = time.perf_counter()


def stop_trace(run: Run):
    import shutil

    import jax

    from . import trace

    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    run.trace_host = (run._trace_t0, t1)
    d = trace_dir(run)
    t2 = time.perf_counter()
    run.trace = trace.reduce_file(trace.find_xplane(d))
    n_ops = sum(len(v) for v in run.trace["devices"].values())
    run.say(f"trace: closed at {t1 - run.t_start:.1f} s from process start, "
            f"profiler stopped in {t2 - t1:.1f} s; {n_ops} device ops on "
            f"{len(run.trace['devices'])} device(s), "
            f"{len(run.trace['spans'])} benchmark spans, reduced in "
            f"{time.perf_counter() - t2:.1f} s")
    if os.environ.get("BENCH_KEEP_TRACE"):
        run.say(f"trace kept at {d}")
    else:
        shutil.rmtree(d, ignore_errors=True)


def init_fleet(**degrees):
    """fleet.init for the given hybrid degrees (recipe of chip_smoke
    ``_init_fleet``, PR 21)."""
    from paddle_tpu.distributed import collective, fleet, mesh, topology

    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = degrees
    fleet.init(is_collective=True, strategy=strategy)


def build_model(run: Run, shardings_of=None, **cfg_extra):
    """The program's GPT with the benchmark's seeded weights in it, in the
    type the configuration states. ``shardings_of(model)`` gives the layout
    the runner's program keeps each parameter in (None: the default device).
    The model is constructed on the host where more than one chip is used
    (its own float32 initial values, thrown away here, would not fit one
    chip at the four-chip cell's width)."""
    import jax

    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    from . import weights

    m = run.config["model"]
    cfg = GPTConfig(vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
                    num_layers=m["num_layers"], num_heads=m["num_heads"],
                    intermediate_size=m["intermediate_size"],
                    max_seq_len=m["max_seq_len"],
                    layer_norm_eps=m["layer_norm_eps"],
                    initializer_range=m["initializer_range"],
                    dropout=0.0, **cfg_extra)
    with run.phase("model_construct"):
        if len(run.devices) > 1:
            with jax.default_device(jax.devices("cpu")[0]):
                model = GPTForCausalLM(cfg)
        else:
            model = GPTForCausalLM(cfg)
    with run.phase("weights"):
        named = dict(model.named_parameters())
        shapes = {n: tuple(p._value.shape) for n, p in named.items()}
        sh = shardings_of(model) if shardings_of else None
        vals = weights.make(run.seed, shapes, m["initializer_range"],
                            m["dtype"], sh)
        for n, p in named.items():
            p._set_value_raw(vals[n])
        del vals
        model.astype(m["dtype"])  # the public recipe; the values already are
        jax.block_until_ready([p._value for p in named.values()])
    return model, shapes

"""One run of one cell: build, warm up, check, measure, report.

``run_cell`` is what ``benchmarks/run.py`` calls with ``platform="tpu"``
and what the tests call with a tiny configuration and ``platform="cpu"``.
It runs in the calling process, which becomes the one jax process: nothing
is started, nothing outlives it but the compile cache.

The order of a run:

1. the cell's files are found by the names in ``BENCHMARK.json``
   (``spec.py``) and every reader and reference is imported, so a missing
   file fails by name before the backend comes up;
2. the backend comes up and must be ``platform`` with at least the chips
   the cell asks for;
3. weights are made on the device from ``--seed`` (``params.py``), the
   ``ContinuousBatcher`` and its ``Engine`` are built at the configuration's
   pool geometry (under the configuration's mesh where it has one);
4. warm-up: one request of every prompt length of the mix, greedy and
   sampled, so that the decode program, each prefill program and the small
   eager programs around them exist before the window. Only this cell's
   shapes;
5. part (a) of ``correct``: seeded requests through the engine against the
   plain reference (``check.py``);
6. the callers start; the window opens when each has had a request
   admitted, and lasts ``--seconds``. With ``--trace 1`` the program's
   ``ServingMonitor`` and ``DeviceMonitor`` are attached and a slice of the
   window is recorded by the profiler;
7. the window closes: what is in flight is cancelled, and one finished
   greedy request is run again alone and must give the same tokens — on
   one chip, for configurations whose outputs do not depend on the batch
   (``moe_exact``). Not under a mesh: a row's all-reduce is summed in an
   order that depends on where the row sits in the batch, so the same
   request in another row rounds differently, and over 128 greedy tokens
   it differed in 3 of 7 runs at tp=4 (PERF.md section 6);
8. the last line printed is the result.

``setup_s`` is everything before the window opens, from the start of the
process.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import shutil
import time
from pathlib import Path

import numpy as np

from benchmarks.lib import check, driver, spec, traffic, xplane
from benchmarks.lib.params import seeded_params
from benchmarks.lib.peaks import peaks_for

# where in the window the profiler records, and for how long: late enough
# that the callers are out of step, short enough that the trace stays small
TRACE_START_S = 5.0
TRACE_SECONDS = 3.0

# published key -> TransformerConfig field, for the keys both files carry:
# the ``transformer_config`` group is what runs, and must say what the
# published keys beside it say
KEYMAP = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "sliding_window": "sliding_window",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k",
}
RMS_NORM_EPS_OF_THE_PROGRAM = 1e-5  # transformer.rms_norm's fixed default


class CellError(RuntimeError):
    """The cell cannot run as described (no result is printed)."""


@dataclasses.dataclass
class RunData:
    """What a per-layer reader may read. ``steps`` and ``compiles`` come
    from the program's monitors (traced runs attach them), ``trace`` from
    the profiler, the rest from the harness."""

    cfg: dict  # the configuration file
    chips: int
    peaks: dict | None  # None off the TPU (tests)
    memory_peak_bytes: int
    e2e: dict  # driver.end_to_end of the window
    window: tuple  # (open, close) on the host clock
    flights: list  # driver.Flight, every request touched by the window
    loop_steps: list  # driver.Step inside the window
    steps: list  # ServingMonitor step records inside the window
    compiles_in_window: int | None
    trace: xplane.Trace | None = None
    slice: tuple | None = None  # (lo, hi) of the traced slice, trace clock
    # host clock: from before the profiler was started to the end of the
    # first step after it was stopped (starting and stopping stall the loop)
    profiler_span: tuple | None = None
    slice_steps: list = dataclasses.field(default_factory=list)

    @property
    def dims(self) -> dict:
        return self.cfg["transformer_config"]

    @property
    def pool(self) -> dict:
        return self.cfg["pool"]

    @functools.cached_property
    def step_busy(self) -> dict[int, float]:
        """Seconds of device work inside each traced step's span, by step
        index (empty without a trace)."""
        if self.trace is None or self.slice is None:
            return {}
        return xplane.step_busy_seconds(
            self.trace, driver.SPAN_STEP, [s.index for s in self.slice_steps]
        )

    @property
    def decode_only_steps(self) -> list:
        """The traced steps that delivered tokens and admitted nothing:
        what they ran on the device is one decode step."""
        return [
            s for s in self.slice_steps
            if s.index in self.step_busy and s.delivered
            and not s.admitted_prompt_tokens
        ]


def transformer_config(transformer_module, cfg: dict):
    """The program's ``TransformerConfig`` from the configuration file's
    ``transformer_config`` group, refused where it contradicts the
    published keys beside it or asks for what the program does not do."""
    import jax.numpy as jnp

    fields = dict(cfg["transformer_config"])
    for published, field in KEYMAP.items():
        if published in cfg and field in fields and cfg[published] != fields[field]:
            raise CellError(
                f"config {cfg['name']!r}: {published}={cfg[published]!r} but "
                f"transformer_config.{field}={fields[field]!r}"
            )
    if cfg.get("rms_norm_eps", RMS_NORM_EPS_OF_THE_PROGRAM) != RMS_NORM_EPS_OF_THE_PROGRAM:
        raise CellError(
            f"config {cfg['name']!r}: rms_norm_eps {cfg['rms_norm_eps']} is "
            f"not the {RMS_NORM_EPS_OF_THE_PROGRAM} the program fixes"
        )
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("tie_word_embeddings"):
        raise CellError(
            f"config {cfg['name']!r}: the program has SwiGLU and untied "
            "embeddings only"
        )
    fields["dtype"] = getattr(jnp, fields.pop("dtype", "bfloat16"))
    return transformer_module.TransformerConfig(**fields)


def device_identity(jax) -> dict:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no such count: the CPU of the tests)."""
    peak = 0
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(
    root: str | Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    platform: str = "tpu",
    t_process_start: float | None = None,
    log=print,
    keep_trace: str | Path | None = None,
    clock=time.perf_counter,
) -> dict:
    """Run one cell once and return the result object (the caller prints it
    as the last line). Raises before any result where the cell cannot run.
    ``clock`` is the host clock everything is stamped with (the tests count
    steps with a clock of their own, so that a loaded machine does not
    shorten their windows)."""
    t_start = clock() if t_process_start is None else t_process_start
    root = Path(root)

    # 1. the cell's files, by name
    bench = spec.load_benchmark(root)
    cell = spec.cell(root, bench, workload)
    cfg, chips = cell["config"], cell["workload"]["chips"]
    mix = traffic.load_mix(cell["mix_path"])
    reference = spec.reference(root, bench, cfg["reference"])
    readers = {
        m["name"]: spec.layer_metric_reader(root, bench, m["name"])
        for m in cell["per_layer"]
    } if trace else {}
    pool = cfg["pool"]
    if traffic.longest_request(mix) > pool["max_pages_per_seq"] * pool["page_size"]:
        raise CellError(
            f"mix {mix['name']!r} has requests of {traffic.longest_request(mix)} "
            f"tokens; config {cfg['name']!r} holds "
            f"{pool['max_pages_per_seq'] * pool['page_size']} a row"
        )

    # 2. the backend
    import jax

    device = device_identity(jax)
    if device["platform"] != platform or device["count"] < chips:
        raise CellError(
            f"cell {workload!r} needs {chips} {platform} chip(s); jax found "
            f"{device}"
        )
    peaks = peaks_for(device["kind"]) if platform == "tpu" else None
    devices = jax.devices()[:chips]
    t_backend = clock()

    # 3. the system under test
    from jax.sharding import NamedSharding, PartitionSpec

    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.engine import Engine
    from bee_code_interpreter_tpu.models.serving import (
        ContinuousBatcher,
        SamplingParams,
    )

    tconfig = transformer_config(T, cfg)
    mesh = shardings = None
    if cfg.get("mesh"):
        from bee_code_interpreter_tpu.parallel import make_mesh

        mesh = make_mesh(dict(cfg["mesh"]), devices=devices)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            T.param_specs(tconfig, mesh),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    params = jax.block_until_ready(
        seeded_params(T.init_params, tconfig, seed, shardings)
    )
    t_params = clock()
    batcher = ContinuousBatcher(params, tconfig, mesh=mesh, **pool)
    engine = Engine(batcher)
    serving_monitor = device_monitor = None
    if trace:
        from bee_code_interpreter_tpu.observability import (
            DeviceMonitor,
            ServingMonitor,
        )

        serving_monitor = ServingMonitor(max_steps=1 << 16, max_requests=1 << 14)
        device_monitor = DeviceMonitor()
        serving_monitor.attach(engine)
        device_monitor.attach(engine)
    t_built = clock()

    # 4. warm-up: every prompt length of the mix, greedy and sampled
    vocab = tconfig.vocab_size
    rng = np.random.default_rng([seed, 0xBE7C4])
    lengths = traffic.prompt_lengths(mix)
    sampled = SamplingParams(
        temperature=mix["sampling"]["temperature"],
        top_p=mix["sampling"].get("top_p"), seed=seed, logprobs=True,
    )
    kinds = [SamplingParams(), sampled]
    for i, length in enumerate(lengths + lengths[:1]):
        engine.submit(
            rng.integers(0, vocab, length, dtype=np.int32), 2,
            sampling=kinds[i % 2],
        )
    engine.run_to_completion()
    t_warm = clock()

    # 5. correct (a): the system against the plain reference
    check_len = lengths[0]
    checked, (solo_prompt, solo_tokens) = check.against_reference(
        engine, reference, params, cfg, SamplingParams, seed,
        prompts=[
            rng.integers(0, vocab, check_len, dtype=np.int32)
            for _ in range(reference.TOLERANCE["requests"])
        ],
    )
    problems = list(checked["problems"])
    log("CHECK " + json.dumps({
        "reference": cfg["reference"], "prompt_tokens": check_len,
        "tolerance": reference.TOLERANCE, **checked,
    }))
    t_checked = clock()

    # 6. the callers and the window
    streams = traffic.client_streams(mix, seed, vocab)
    loop = driver.ClosedLoop(
        engine, streams, mix, SamplingParams, vocab, clock=clock,
        annotate=jax.profiler.TraceAnnotation,
    )
    while len(loop.started) < len(streams):
        loop.step()
        if len(loop.steps) > 100_000:
            raise CellError("callers were never all admitted")
    compiles_before = (
        device_monitor.snapshot(recent=0)["compile"]["total"] if trace else None
    )
    first_step = len(loop.steps)
    unix_open = time.time()
    t_open = clock()
    setup_s = t_open - t_start

    trace_dir = root / ".bench_out" / "trace" / workload
    tracing = traced = False
    slice_first = slice_last = t_slice = t_profiler = None
    trace_start = min(TRACE_START_S, 0.3 * seconds)
    trace_seconds = min(TRACE_SECONDS, 0.3 * seconds)
    while clock() - t_open < seconds:
        if trace and not tracing and not traced and clock() - t_open >= trace_start:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            # python_tracer_level 0: per-call hooks on the host loop would
            # stretch exactly the gaps the idle share measures
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            t_profiler = clock()
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            tracing, slice_first, t_slice = True, len(loop.steps), clock()
        loop.step()
        if tracing and clock() - t_slice >= trace_seconds:
            jax.profiler.stop_trace()
            tracing, traced, slice_last = False, True, len(loop.steps)
    if tracing:
        jax.profiler.stop_trace()
        traced, slice_last = True, len(loop.steps)
    t_close = loop.steps[-1].t_end
    unix_close = time.time()

    # 7. close the window; the solo re-run
    flights = loop.finished + list(loop.live.values())
    window_steps = loop.steps[first_step:]
    compiles_in_window = (
        device_monitor.snapshot(recent=0)["compile"]["total"] - compiles_before
        if trace else None
    )
    loop.drain()
    solo = None
    if tconfig.moe_exact and mesh is None:
        done = [
            f for f in loop.finished
            if f.tokens is not None and f.t_done is not None and f.t_done > t_open
        ]
        if done:
            flight = min(done, key=lambda f: len(f.tokens))
            prompt, want = flight.request.prompt, flight.tokens
        else:  # too short a window (tests): the greedy check request
            prompt, want = solo_prompt, solo_tokens
        ticket = engine.submit(prompt, len(want), sampling=SamplingParams())
        engine.run_to_completion()
        solo = engine.result(ticket) == list(want)
        engine.release(ticket)
        if not solo:
            problems.append(
                f"a greedy request of {len(want)} tokens decoded alone "
                "differs from its tokens in the batch"
            )
    problems += loop.problems
    e2e = driver.end_to_end(flights, t_open, t_close, mix)
    e2e["setup_s"] = setup_s
    peak = memory_peak_bytes(devices)

    log("SETUP " + json.dumps({
        "backend_s": t_backend - t_start, "params_s": t_params - t_backend,
        "build_s": t_built - t_params, "warmup_s": t_warm - t_built,
        "check_s": t_checked - t_warm, "ramp_s": t_open - t_checked,
        "setup_s": setup_s,
    }))
    log("SAMPLES " + json.dumps({
        "window_s": e2e["window_s"], "steps": len(window_steps),
        "tokens": e2e["tokens"], "ttft": e2e["n_ttft_by_length"],
        "gaps": e2e["n_gaps"],
        "finished": sum(
            1 for f in loop.finished if f.t_done is not None and f.t_done > t_open
        ),
        "solo_rerun_equal": solo, "compiles_in_window": compiles_in_window,
    }))
    for problem in problems[:20]:
        log("PROBLEM " + problem)

    # 8. the result
    result = {
        "correct": not problems,
        "attempted": loop.attempted,
        "failed": loop.failed,
        "metrics": {},
        "device": {**device, "memory_peak_bytes": peak},
    }
    if not trace:
        for metric in cell["end_to_end"]:
            if metric["name"] not in e2e:
                raise CellError(
                    f"the window gave no sample for {metric['name']!r}"
                )
            result["metrics"][metric["name"]] = {
                "value": e2e[metric["name"]], "unit": metric["unit"],
            }
        return result

    run = RunData(
        cfg=cfg, chips=chips, peaks=peaks,
        memory_peak_bytes=peak, e2e=e2e, window=(t_open, t_close),
        flights=flights,
        loop_steps=window_steps,
        steps=[
            s for s in serving_monitor.snapshot(steps=1 << 16)["steps"]["last"]
            if unix_open < s["ts"] <= unix_close
        ],
        compiles_in_window=compiles_in_window,
    )
    if traced:
        path = xplane.find_xplane(trace_dir)
        run.trace = xplane.load(path, platform)
        run.slice_steps = loop.steps[slice_first:slice_last]
        run.profiler_span = (
            t_profiler,
            loop.steps[min(slice_last, len(loop.steps) - 1)].t_end,
        )
        spans = xplane.step_spans(run.trace, driver.SPAN_STEP)
        inside = [spans[s.index] for s in run.slice_steps if s.index in spans]
        if inside and run.trace.devices:
            lo, hi = inside[0].start, inside[-1].end
            run.slice = (lo, hi)
            busy = xplane.busy_seconds(run.trace, lo, hi)
            result["device"]["busy_s"] = sum(busy) / len(busy)
            result["device"]["window_s"] = hi - lo
            result["breakdown"] = xplane.breakdown(
                run.trace, lo, hi, driver.SPAN_STEP
            )
            log("TRACE " + json.dumps({
                "xplane_bytes": path.stat().st_size,
                "devices": [d.name for d in run.trace.devices],
                "busy_s": busy, "slice_s": hi - lo,
                "slice_steps": len(run.slice_steps),
                "modules": sorted({
                    m.name for d in run.trace.devices for m in d.modules
                })[:40],
            }))
        if keep_trace is not None:
            Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(path, keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    by_name = {m["name"]: m for m in cell["per_layer"]}
    for name, reader in readers.items():
        value = reader.read(run)
        if value is not None:
            result["metrics"][name] = {
                "value": value, "unit": by_name[name]["unit"],
            }
    return result

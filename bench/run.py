#!/usr/bin/env python3
"""On-chip benchmark of NeoEngine's served path.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>
.json``: the published config, its cut and the engine settings) and a traffic
mix (``bench/traffic/<name>.json``, read by ``bench/loadgen.py``).  A run:

1. fails (exit 2, no result) unless JAX finds a TPU with the chips the cell
   asks for; ``--rehearse`` runs the configuration's small ``rehearse`` sizes
   on whatever JAX finds (tests only);
2. makes the weights from ``--seed`` on the device (``bench/weights.py``),
   builds ``NeoEngine`` (policy ``neo``, greedy), compiles every shape the
   traffic can reach (``bench/warmup.py``);
3. drives ``repro.launch.serve.run_online`` with the seeded requests: a
   lead-in, then the measured window of ``--seconds``, then (open-loop
   mixes) a capped drain until the window's requests finish.  ``setup_s``
   is everything before the window;
4. with ``--trace 1``, records the profiler trace of the window and reduces
   it (``bench/trace_reduce.py``);
5. reads each metric of the cell through its reader, ``bench/metrics/
   <name>.py`` (end-to-end metrics with ``--trace 0``, per-layer ones with
   ``--trace 1``);
6. frees the program's state and checks what the window served against the
   plain float32 reference (``bench/check.py``);
7. prints the compared numbers beside their limits on standard error, and
   as its last line of standard output one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``;
   ``checks`` comes last.

``--control`` judges the check's control (the reference in fp8) in the
program's place, through the same comparison and limits, and reports its
``correct``; the program's own reading is logged beside it.  The benchmark's
runs never use it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class StopRun(Exception):
    """Raised between engine steps when the run has what it measures."""


class CompileLog:
    """Backend compiles (and persistent-cache loads) from JAX's own
    monitoring events, with the name of each (copied from chip_smoke.py)."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.names: List[str] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, fun_name=None, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += duration
                self.names.append(str(fun_name))


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def model_dims(cfgj: Dict) -> Dict:
    H = cfgj["num_attention_heads"]
    d = cfgj["hidden_size"]
    return {
        "L": cfgj["num_hidden_layers"], "d": d, "H": H,
        "KV": cfgj["num_key_value_heads"], "hd": cfgj.get("head_dim", d // H),
        "f": cfgj["intermediate_size"], "V": cfgj["vocab_size"],
        "tied": bool(cfgj["tie_word_embeddings"]), "qk_norm": bool(cfgj["qk_norm"]),
        "eps": float(cfgj["rms_norm_eps"]), "theta": float(cfgj["rope_theta"]),
        "dtype": cfgj["torch_dtype"],
    }


def load_config(name: str, rehearse: bool) -> Dict:
    cfgj = load_json(HERE, "configs", name + ".json")
    if rehearse:
        small = dict(cfgj["rehearse"])
        engine = small.pop("engine")
        cfgj = {**cfgj, **small, "engine": engine}
        if "head_dim" not in small:
            cfgj["head_dim"] = small["hidden_size"] // small["num_attention_heads"]
    return cfgj


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a cell reports: its end-to-end ones untraced, its
    per-layer ones traced."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]


class Probe:
    """The benchmark's wraps of the engine's entry points: per-request
    times, the window's phases, host-attention bytes and time, and the work
    each dispatch did (for ``mfu`` and the kernel's roofline)."""

    def __init__(self, engine, mix: Dict, seconds: float, traced: bool,
                 compiles: CompileLog, dims: Dict, trace_dir: Optional[str]):
        self.engine, self.mix, self.seconds = engine, mix, seconds
        self.traced, self.compiles, self.dims = traced, compiles, dims
        self.trace_dir = trace_dir
        self.t0 = math.inf
        self.phase = "lead"
        self.w0 = self.w1 = None
        self.records: List[Dict] = []
        self.by_rid: Dict[int, Dict] = {}
        self.tokens = 0
        self.steps = 0
        self.marks: Dict[str, Dict] = {}
        self._lock = threading.Lock()
        self.host_attn = {"bytes": 0, "seconds": 0.0, "calls": 0}
        self.work = {"prefill_tokens": 0, "prefill_requests": 0, "attn_pairs": 0,
                     "decode_rows": 0, "device_ctx": 0, "kernel_bytes": 0,
                     "decode_calls": 0}
        self.prefill_shapes: Dict[str, int] = {}
        self.host_rids = set()
        self.swap_rids = set()
        self._window_span = None
        self._install()

    # -- wraps -------------------------------------------------------------
    def _annotate(self, name, fn):
        if not self.traced:
            return fn
        import jax

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    def _install(self) -> None:
        eng, ex = self.engine, self.engine.executor
        offer, step = eng.offer, eng.step

        def offer_w(prompt, max_new_tokens, *, arrival_time=0.0, **kw):
            rid = offer(prompt, max_new_tokens, arrival_time=arrival_time, **kw)
            rec = {"arrival": arrival_time, "rid": rid,
                   "prompt_len": len(prompt), "output_len": max_new_tokens,
                   "first": None, "finish": None, "n": 0,
                   "status": "rejected" if rid is None else "active"}
            self.records.append(rec)
            if rid is not None:
                self.by_rid[rid] = rec
            return rid

        def step_w(now=None):
            t_in = time.perf_counter()
            self.t0 = min(self.t0, t_in - now)
            emitted = step(now=now)
            t = time.perf_counter()
            self.steps += 1
            self.tokens += len(emitted)
            for rid, _ in emitted:
                rec = self.by_rid[rid]
                rec["n"] += 1
                if rec["first"] is None:
                    rec["first"] = t
                if rec["n"] >= rec["output_len"]:
                    rec["finish"] = t
                    rec["status"] = "finished"
            self._advance(t)
            return emitted

        eng.offer = offer_w
        eng.step = self._annotate("bench.step", step_w)
        eng._emit = self._annotate("bench.sample", eng._emit)

        prefill = ex.prefill

        def prefill_w(reqs, to_host, extras_fn=None):
            if self.phase == "window":
                self._count_prefill(reqs)
            return prefill(reqs, to_host, extras_fn)
        ex.prefill = self._annotate("bench.prefill", prefill_w)

        decode0 = ex.decode_batch0

        def decode0_w(rows, host_flags, window=0):
            if self.phase == "window":
                self._count_decode(rows, host_flags)
            for r, h in zip(rows, host_flags):
                if h:
                    self.host_rids.add(r.rid)
            return decode0(rows, host_flags, window)
        ex.decode_batch0 = self._annotate("bench.decode_batch0", decode0_w)
        ex.decode = ex.decode_batch0

        host_lane = ex.decode_host_lane

        def lane_w(rows, window=0, *, lane=1):
            if self.phase == "window":
                self._count_decode(rows, [True] * len(rows))
            for r in rows:
                self.host_rids.add(r.rid)
            return host_lane(rows, window, lane=lane)
        ex.decode_host_lane = self._annotate("bench.decode_lane", lane_w)

        host = eng.host_attn
        run_layer = host.run_layer
        page_bytes = host.pool_k.dtype.itemsize
        KV, hd = host.pool_k.shape[3], host.pool_k.shape[4]

        def run_layer_w(layer, q, k_new, v_new, *, host_rows, tables, lens,
                        page_ids, offsets, window=0):
            t = time.perf_counter()
            out = run_layer(layer, q, k_new, v_new, host_rows=host_rows,
                            tables=tables, lens=lens, page_ids=page_ids,
                            offsets=offsets, window=window)
            dt = time.perf_counter() - t
            if self.phase == "window" and len(host_rows):
                from bench.costs import host_attn_bytes
                nb = host_attn_bytes(lens, KV, hd, page_bytes)
                with self._lock:
                    self.host_attn["bytes"] += nb
                    self.host_attn["seconds"] += dt
                    self.host_attn["calls"] += 1
            return out
        host.run_layer = self._annotate("bench.host_attn", run_layer_w)

        tr = eng.transfer
        swap_out, swap_in = tr.swap_out, tr.swap_in

        def swap_out_w(req):
            self.swap_rids.add(req.rid)
            return swap_out(req)

        def swap_in_w(req):
            self.swap_rids.add(req.rid)
            return swap_in(req)
        tr.swap_out = self._annotate("bench.swap_out", swap_out_w)
        tr.swap_in = self._annotate("bench.swap_in", swap_in_w)

    def _count_prefill(self, reqs) -> None:
        from bench.costs import prefill_work
        from bench.warmup import _bucket
        S = _bucket(max(r.prefill_len for r in reqs), 16)
        key = f"{len(reqs)}x{S}"
        with self._lock:
            self.prefill_shapes[key] = self.prefill_shapes.get(key, 0) + 1
            for r in reqs:
                toks, pairs = prefill_work(r.prefill_len)
                self.work["prefill_tokens"] += toks
                self.work["attn_pairs"] += pairs
                self.work["prefill_requests"] += 1

    def _count_decode(self, rows, host_flags) -> None:
        from bench.costs import paged_decode_bytes
        page = self.engine.pool.page_size
        dev_lens = [r.kv_len + 1 for r, h in zip(rows, host_flags) if not h]
        with self._lock:
            self.work["decode_rows"] += len(rows)
            self.work["device_ctx"] += sum(dev_lens)
            if dev_lens:
                self.work["decode_calls"] += 1
                self.work["kernel_bytes"] += paged_decode_bytes(
                    dev_lens, page, self.dims)

    # -- phases ------------------------------------------------------------
    def _snapshot(self) -> Dict:
        st = self.engine.stats
        return {"t": time.perf_counter(), "tokens": self.tokens,
                "steps": self.steps, "compiles": self.compiles.count,
                "offloaded_decodes": st.offloaded_decodes,
                "device_decodes": st.device_decodes,
                "swap_wait_time": st.swap_wait_time,
                "swap_out_bytes": st.swap_out_bytes,
                "swap_in_bytes": st.swap_in_bytes,
                "host_busy_time": st.host_busy_time,
                "rejected": st.rejected_requests}

    def _advance(self, t: float) -> None:
        if self.phase == "lead" and t - self.t0 >= self.mix["lead_in_s"]:
            self.marks["w0"] = self._snapshot()
            self.w0 = self.marks["w0"]["t"]
            self.phase = "window"
            log(f"window opens after {self.w0 - T_START:.3f}s, {self.steps} steps")
            if self.traced:
                import jax
                jax.profiler.start_trace(self.trace_dir, profiler_options=_profile_options())
                self._window_span = jax.profiler.TraceAnnotation("bench.window")
                self._window_span.__enter__()
        elif self.phase == "window" and t - self.w0 >= self.seconds:
            self.marks["w1"] = self._snapshot()
            self.w1 = self.marks["w1"]["t"]
            self.phase = "drain"
            log(f"window closes after {self.w1 - self.w0:.3f}s")
            if self.traced:
                import jax
                self._window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
            if self.mix["drain_cap_s"] <= 0:
                raise StopRun
        elif self.phase == "drain":
            due = [r for r in self.records
                   if self.w0 <= self.t0 + r["arrival"] < self.w1]
            if (all(r["status"] != "active" for r in due)
                    or t - self.w1 >= self.mix["drain_cap_s"]):
                raise StopRun

    def finalize(self) -> None:
        for r in self.records:
            r["due"] = self.t0 + r["arrival"]


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def device_info(devices) -> Dict:
    d = devices[0]
    stats = [x.memory_stats() or {} for x in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    limit = max((s.get("bytes_limit", 0) for s in stats), default=0)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": int(peak), "bytes_limit": int(limit)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes, any platform (tests only)")
    ap.add_argument("--control", action="store_true",
                    help="judge the check's control (fp8 reference) instead")
    args = ap.parse_args(argv)

    # The persistent compilation cache sits at a fixed path inside the
    # checkout, whatever the environment names (a rehearsal, run by tests,
    # keeps the one it is given); the program takes it from this variable.
    cache = os.path.join(ROOT, ".jax_cache")
    if args.rehearse:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", cache)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2

    import jax
    devices = jax.devices()[: cell["chips"]]
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse:
        if platform != "tpu":
            log(f"needs a TPU; JAX found platform {platform!r} ({kind})")
            return 2
        if len(jax.devices()) < cell["chips"]:
            log(f"the cell needs {cell['chips']} chips; JAX found {len(jax.devices())}")
            return 2
    peaks_all = load_json(HERE, "peaks.json")["devices"]
    if kind not in peaks_all and not args.rehearse:
        log(f"no peaks for device kind {kind!r} in bench/peaks.json")
        return 2
    peaks = peaks_all.get(kind)

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction, so no cache-wide file lock for the parallel compiles
    jax.config.update("jax_compilation_cache_max_size", -1)

    from bench import check, loadgen, trace_reduce, warmup, weights
    from repro.config import ArchConfig, EngineConfig
    from repro.core.engine import NeoEngine
    from repro.launch.serve import run_online

    compiles = CompileLog()
    cfgj = load_config(cell["config"], args.rehearse)
    dims = model_dims(cfgj)
    mix = loadgen.load_mix(cell["traffic"], args.rehearse)
    arch = ArchConfig(
        name=cell["config"], family="dense", num_layers=dims["L"], d_model=dims["d"],
        num_heads=dims["H"], num_kv_heads=dims["KV"], head_dim=dims["hd"],
        d_ff=dims["f"], vocab_size=dims["V"], qk_norm=dims["qk_norm"],
        rope_theta=dims["theta"], rms_eps=dims["eps"], tie_embeddings=dims["tied"],
        param_dtype=dims["dtype"], activation_dtype=dims["dtype"])
    ecfg = EngineConfig(policy="neo", decode_sample="greedy",
                        seed=args.seed % (2 ** 31), **cfgj["engine"])

    params = weights.make(dims, args.seed)
    jax.block_until_ready(params)
    log(f"weights made after {time.perf_counter() - T_START:.3f}s")
    engine = NeoEngine(arch, ecfg, params=params)
    del params
    t_w = time.perf_counter()
    log(f"engine built after {t_w - T_START:.3f}s")
    def mem_log(msg: str) -> None:
        stats = devices[0].memory_stats() or {}
        log(f"{msg} after {time.perf_counter() - t_w:.3f}s; device bytes in use "
            f"{stats.get('bytes_in_use')}, peak {stats.get('peak_bytes_in_use')}")

    warm = warmup.warm(engine, mix, mem_log)
    log(f"warm-up {time.perf_counter() - t_w:.3f}s: {warm['graphs']} graphs, "
        f"D={warm['D']} MP={warm['MP']} S={warm['S']} (B, S)={warm['prefill']}, "
        f"{warm['pages']} page counts; compiles so far {compiles.count} "
        f"({compiles.seconds:.3f}s)")

    requests = loadgen.make_requests(mix, args.seed, dims["V"], args.seconds)
    from repro.serving.traces import TraceRequest
    trace = [TraceRequest(r["arrival"], len(r["prompt"]), r["output_len"],
                          prompt=r["prompt"]) for r in requests]
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if args.trace else None
    probe = Probe(engine, mix, args.seconds, bool(args.trace), compiles, dims,
                  tmp.name if tmp else None)
    try:
        run_online(engine, trace, vocab=dims["V"], seed=args.seed % (2 ** 31))
    except StopRun:
        pass
    t_end = time.perf_counter()
    if probe.w1 is None:
        log("the traffic ended before the window closed")
        return 1
    probe.finalize()
    dev = device_info(devices)
    engine.close()

    traced = None
    if args.trace:
        sel = (trace_reduce.TPU if platform == "tpu" else trace_reduce.CPU)
        traced = trace_reduce.reduce_dir(tmp.name, sel, span_prefix="bench.")
        tmp.cleanup()
        log("trace planes " + json.dumps(traced["planes"]))
        log("trace device ops " + json.dumps(traced["device_ops"][:25]))
        log("trace idle gaps " + json.dumps(traced["idle_gaps"][:10]))
        log("trace spans " + json.dumps(traced["spans"]))

    w0m, w1m = probe.marks["w0"], probe.marks["w1"]
    ctx = {
        "cell": cell, "mix": mix, "dims": dims, "peaks": peaks,
        "setup_s": probe.w0 - T_START, "window_s": probe.w1 - probe.w0,
        "w0": probe.w0, "w1": probe.w1, "records": probe.records,
        "delta": {k: w1m[k] - w0m[k] for k in w0m},
        "host_attn": dict(probe.host_attn), "work": dict(probe.work),
        "trace": traced, "device": dev, "platform": platform,
        "compile_names": compiles.names[w0m["compiles"]:w1m["compiles"]],
    }
    if ctx["compile_names"]:
        log(f"compiled inside the window: {ctx['compile_names'][:20]}")
    metrics = {}
    for m in cell_metrics(spec, cell["name"], bool(args.trace)):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # -- the check, once the program's state is freed -----------------------
    probe_prefills = dict(probe.prefill_shapes)
    sample = check.pick(engine, probe, args.seed, load_json(HERE, "limits", cell["name"] + ".json"),
                        args.rehearse)
    del engine, probe
    gc.collect()
    mem = devices[0].memory_stats() or {}
    log(f"device bytes in use before the check: {mem.get('bytes_in_use')}")
    t_c = time.perf_counter()
    result = check.run(sample, dims, args.seed, control=args.control)
    log(f"check {time.perf_counter() - t_c:.3f}s over {result['tokens']} served tokens: "
        + json.dumps(result["info"]))

    recs = ctx["records"]
    attempted = sum(1 for r in recs if r["status"] != "active"
                    or (r["first"] is not None))
    failed = sum(1 for r in recs if r["status"] == "rejected")
    if mix["drain_cap_s"] > 0:
        failed += sum(1 for r in recs if ctx["w0"] <= r["due"] < ctx["w1"]
                      and r["status"] == "active")
    device = {k: dev[k] for k in ("platform", "kind", "count", "memory_peak_bytes")}
    out = {"correct": result["correct"], "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = {"device_ops": traced["device_ops"][:10],
                            "idle_gaps": traced["idle_gaps"][:10]}
    extra = {"run_s": time.perf_counter() - T_START, "after_window_s": t_end - ctx["w1"],
             "warm": warm, "work": ctx["work"], "delta": ctx["delta"],
             "window_prefills": probe_prefills,
             "compiles": {"in_window": len(ctx["compile_names"]),
                          "total": compiles.count, "seconds": compiles.seconds}}
    log("detail " + json.dumps(extra))
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out["checks"] = result["checks"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Scheduler property tests (hypothesis): the six-step procedure must
preserve page accounting, respect the no-bubble inequalities, never lose a
request, and never starve one."""

import pytest

try:  # the hypothesis-based property tests skip without the package; the
    # deterministic tests below (starvation, policy, micro-batch annotation)
    # run regardless
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAS_HYPOTHESIS = False

from repro.config import EngineConfig
from repro.configs import get_config
from repro.core.perfmodel import PerfModel
from repro.core.request import Request, RequestState
from repro.core.scheduler import NeoScheduler, PoolView


CFG = get_config("qwen3-0.6b")  # 16-token pages
PAGE = CFG.kv_block_size


def make_scheduler(policy="neo", device=64, host=256, max_tokens=2048):
    ecfg = EngineConfig(device_pool_pages=device, host_pool_pages=host,
                        max_batch_tokens=max_tokens, policy=policy)
    perf = PerfModel.for_arch(CFG, "tpu_v5e")
    return NeoScheduler(CFG, ecfg, perf)


if HAS_HYPOTHESIS:
    reqs_strategy = st.lists(
        st.tuples(st.integers(1, 400),   # prompt_len
                  st.integers(1, 64)),   # max_new
        min_size=1, max_size=24,
    )


class Harness:
    """Page-exact virtual executor mirroring SimEngine's bookkeeping."""

    def __init__(self, sched, device, host):
        self.s = sched
        self.device_free = device
        self.host_free = host
        self.page = PAGE

    def run_iteration(self):
        view = PoolView(self.page, self.device_free, self.host_free,
                        device_total=self.device_free_total(),
                        host_total=self.host_free_total())
        plan = self.s.plan(view)
        if plan.is_empty():
            return None
        for r in plan.preempt:
            self._free(r)
        for r in plan.swap_out:
            n = len(r.pages)
            self.device_free += n
            self.host_free -= n
            assert self.host_free >= 0, "host overcommit on swap_out"
            r.location = "cpu"
        for r in plan.swap_in:
            n = len(r.pages)
            self.host_free += n
            self.device_free -= n
            assert self.device_free >= 0, "device overcommit on swap_in"
            r.location = "gpu"
        self.s.commit(plan)
        for r in plan.prefill:
            n = -(-r.prefill_len // self.page)
            if r in plan.prefill_to_host:
                self.host_free -= n
            else:
                self.device_free -= n
            assert self.device_free >= 0 and self.host_free >= 0, "prefill overcommit"
            r.pages = [0] * n
            if not r.out_tokens:
                r.out_tokens.append(0)
        for r in plan.decode_rows:
            if r in plan.prefill or r.state != RequestState.RUNNING:
                continue
            if r.kv_len % self.page == 0 and r.kv_len // self.page >= len(r.pages):
                if r.location == "cpu":
                    self.host_free -= 1
                else:
                    self.device_free -= 1
                assert self.device_free >= 0 and self.host_free >= 0, "decode overcommit"
                r.pages = r.pages + [0]
            r.out_tokens.append(0)
        for r in plan.prefill + plan.decode_rows:
            if r.state == RequestState.RUNNING and r.is_done():
                r.state = RequestState.FINISHED
                self._free(r)
        self.s.remove_finished()
        return plan

    def _free(self, r):
        if r.location == "cpu":
            self.host_free += len(r.pages)
        else:
            self.device_free += len(r.pages)
        r.pages = []
        r.location = "gpu"

    def device_free_total(self):
        return 64

    def host_free_total(self):
        return 256


if HAS_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(reqs_strategy, st.sampled_from(["neo", "gpu_only", "fastdecode"]))
    def test_scheduler_conserves_and_completes(reqs, policy):
        s = make_scheduler(policy)
        h = Harness(s, 64, 256)
        for i, (pl, mx) in enumerate(reqs):
            s.add_request(Request(rid=i, prompt=[1] * pl, max_new_tokens=mx,
                                  arrival_time=float(i)))
        total_pages = h.device_free + h.host_free
        for it in range(3000):
            plan = h.run_iteration()
            if plan is None:
                break
            # invariant: accounting conserved
            held = sum(len(r.pages) for r in s.gpu_runq + s.cpu_runq)
            assert h.device_free + h.host_free + held == total_pages
            # invariant: no request appears twice in one plan
            ids = [id(r) for r in plan.decode_rows]
            assert len(ids) == len(set(ids))
        # every admitted request finished; the rest were aborted, never lost
        assert s.num_queued == 0

    @settings(max_examples=20, deadline=None)
    @given(reqs_strategy)
    def test_neo_plans_respect_inequalities(reqs):
        """Chosen asym plans keep T_ca1<=T_l0 and T_ca0<=T_l1+T_ga0 within
        the starvation-override allowance."""
        s = make_scheduler("neo")
        h = Harness(s, 64, 256)
        all_reqs = []
        for i, (pl, mx) in enumerate(reqs):
            r = Request(rid=i, prompt=[1] * pl, max_new_tokens=mx,
                        arrival_time=float(i))
            all_reqs.append(r)
            s.add_request(r)
        slack = 1.15  # forced (anti-starvation) rows may exceed slightly
        for it in range(2000):
            plan = h.run_iteration()
            if plan is None:
                break
            if plan.mode == "asym" and not plan.preempt:
                st_ = plan.stages
                if st_.t_ca1 > 0 and not any(r.skipped for r in plan.decode_cpu1):
                    assert st_.t_ca1 <= slack * max(st_.t_l0, 1e-9) \
                        or len(plan.decode_cpu1) <= len(plan.swap_out) + 1
        for r in all_reqs:
            assert r.state in (RequestState.FINISHED, RequestState.ABORTED)
            if r.state == RequestState.FINISHED:
                assert len(r.out_tokens) == r.max_new_tokens
else:  # visible skips so missing property coverage never passes silently
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_scheduler_conserves_and_completes():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_neo_plans_respect_inequalities():
        pass


def test_no_starvation():
    """A request never waits more than starvation_limit+O(1) iterations
    without progress once admitted to the CPU queue."""
    s = make_scheduler("neo", device=8, host=64, max_tokens=512)
    h = Harness(s, 8, 64)
    for i in range(8):
        s.add_request(Request(rid=i, prompt=[1] * 60, max_new_tokens=24,
                              arrival_time=float(i)))
    last_progress = {i: 0 for i in range(8)}
    lens = {i: 0 for i in range(8)}
    reqs = list(s.waitq)
    for it in range(2000):
        plan = h.run_iteration()
        if plan is None:
            break
        for r in reqs:
            if len(r.out_tokens) > lens[r.rid]:
                lens[r.rid] = len(r.out_tokens)
                last_progress[r.rid] = it
            if r.state == RequestState.RUNNING and r.location == "cpu":
                stall = it - last_progress[r.rid]
                assert stall <= 4 * s.engine_cfg.starvation_limit + 8, \
                    f"rid {r.rid} stalled {stall} iterations"
    assert all(r.state == RequestState.FINISHED for r in reqs)


@pytest.mark.parametrize("policy", ["neo", "gpu_only"])
def test_prompt_of_the_whole_budget_prefills_beside_decode_rows(policy):
    """A prompt as long as max_batch_tokens is prefilled while other rows
    decode: counted against the decode rows' tokens, it would wait at the
    queue's head for as long as any row decodes."""
    s = make_scheduler(policy, device=64, host=256, max_tokens=256)
    h = Harness(s, 64, 256)
    s.add_request(Request(rid=0, prompt=[1] * 32, max_new_tokens=64,
                          arrival_time=0.0))
    s.add_request(Request(rid=1, prompt=[1] * 256, max_new_tokens=8,
                          arrival_time=1.0))
    first = h.run_iteration()
    assert [r.rid for r in first.prefill] == [0]
    second = h.run_iteration()
    assert [r.rid for r in second.prefill] == [1]
    assert [r.rid for r in second.decode_rows] == [0]


def test_gpu_only_never_offloads_decode():
    s = make_scheduler("gpu_only")
    h = Harness(s, 64, 256)
    for i in range(10):
        s.add_request(Request(rid=i, prompt=[1] * 100, max_new_tokens=16,
                              arrival_time=float(i)))
    for it in range(1000):
        plan = h.run_iteration()
        if plan is None:
            break
        assert not plan.decode_cpu0 and not plan.decode_cpu1


def _running_host_rows(sched, n, kv_tokens=40):
    """Seed the CPU runqueue with RUNNING host-resident decode rows."""
    rows = []
    for i in range(n):
        r = Request(rid=100 + i, prompt=[1] * kv_tokens, max_new_tokens=16,
                    arrival_time=float(i))
        r.state = RequestState.RUNNING
        r.location = "cpu"
        r.out_tokens = [0]
        r.pages = [0] * (-(-(r.kv_len + 1) // PAGE))
        rows.append(r)
        sched.cpu_runq.append(r)
    return rows


def test_microbatch_annotated_on_batch1_only_plans():
    """A plan with NO batch-0 lane and >= 2 host rows must carry the
    micro-batch annotation with a split strictly inside the row list."""
    s = make_scheduler("fastdecode")
    _running_host_rows(s, 4)
    plan = s.plan(PoolView(PAGE, 64, 256))
    assert not plan.prefill and not plan.decode_gpu and not plan.decode_cpu0
    assert len(plan.decode_cpu1) == 4
    assert plan.microbatch
    assert 1 <= plan.microbatch_split < len(plan.decode_cpu1)
    assert plan.est_iter_time > 0


def test_microbatch_not_annotated_with_batch0_or_single_row():
    # a prefill gives batch-1 a device lane to hide under: no split
    s = make_scheduler("fastdecode")
    _running_host_rows(s, 3)
    s.add_request(Request(rid=0, prompt=[1] * 40, max_new_tokens=4))
    plan = s.plan(PoolView(PAGE, 64, 256))
    assert plan.prefill and not plan.microbatch
    # a single host row cannot be split
    s2 = make_scheduler("fastdecode")
    _running_host_rows(s2, 1)
    plan2 = s2.plan(PoolView(PAGE, 64, 256))
    assert len(plan2.decode_cpu1) == 1 and not plan2.microbatch


def test_microbatch_disabled_by_config_and_serial_mode():
    ecfg = EngineConfig(device_pool_pages=64, host_pool_pages=256,
                        max_batch_tokens=2048, policy="fastdecode",
                        microbatch=False)
    s = NeoScheduler(CFG, ecfg, PerfModel.for_arch(CFG, "tpu_v5e"))
    _running_host_rows(s, 4)
    plan = s.plan(PoolView(PAGE, 64, 256))
    assert not plan.microbatch and plan.microbatch_split == 0
    # policy="simple" emits mode="serial" plans: never micro-batched
    s2 = make_scheduler("simple")
    _running_host_rows(s2, 4)
    plan2 = s2.plan(PoolView(PAGE, 64, 256))
    assert plan2.mode == "serial" and not plan2.microbatch


def test_microbatch_split_balances_kv():
    """When host attention dominates (long KV), the perf-model split
    balances the two lanes' attention load near the middle."""
    s = make_scheduler("fastdecode")
    _running_host_rows(s, 6, kv_tokens=20_000)  # t_cpu_attn >> t_linear
    plan = s.plan(PoolView(PAGE, 64, 1 << 20))
    assert plan.microbatch
    assert 2 <= plan.microbatch_split <= 4  # near-balanced
    kv = [r.kv_len + 1 for r in plan.decode_cpu1]
    a = sum(kv[: plan.microbatch_split])
    assert 0.3 <= a / sum(kv) <= 0.7


def _balanced_lanes(kv, k):
    n = len(kv)
    lanes, prev = [], 0
    for b in [round(i * n / k) for i in range(1, k)] + [n]:
        lanes.append((b - prev, sum(kv[prev:b])))
        prev = b
    return lanes


def test_fill_drain_lets_deep_splits_win():
    """S2 forcing test: the steady-state period alone never prefers K > 2
    (resource totals only grow with K); the fill/drain term must make a
    balanced K=3 beat the BEST K=2 split when host attention dominates."""
    perf = make_scheduler("fastdecode").perf
    kv = [20_000] * 6  # t_cpu_attn >> t_linear per lane
    best2 = min(perf.lane_plan_time([(k, sum(kv[:k])), (6 - k, sum(kv[k:]))])
                for k in range(1, 6))
    assert perf.lane_plan_time(_balanced_lanes(kv, 3)) < best2
    # and when linear dominates (tiny KV) deeper splits must NOT win: each
    # extra lane adds a dispatch to the device total with nothing to hide
    kv_s = [8] * 6
    best2_s = min(perf.lane_plan_time([(k, sum(kv_s[:k])), (6 - k, sum(kv_s[k:]))])
                  for k in range(1, 6))
    assert perf.lane_plan_time(_balanced_lanes(kv_s, 6)) >= best2_s


def test_scheduler_picks_deep_lane_split():
    """End-to-end: host-attention-dominant rows drive the planner past the
    classic two-lane micro-batch split."""
    s = make_scheduler("fastdecode")
    _running_host_rows(s, 6, kv_tokens=20_000)
    plan = s.plan(PoolView(PAGE, 64, 1 << 20))
    assert plan.num_host_lanes >= 3
    assert len(plan.lane_splits) == plan.num_host_lanes - 1


def test_queue_surface_and_admission():
    """Continuous-batching surface: waiting/running/swapped views and the
    max_waiting admission cap."""
    ecfg = EngineConfig(device_pool_pages=64, host_pool_pages=256,
                        max_batch_tokens=2048, policy="gpu_only",
                        max_waiting=2)
    s = NeoScheduler(CFG, ecfg, PerfModel.for_arch(CFG, "tpu_v5e"))
    assert s.has_capacity()
    s.add_request(Request(rid=0, prompt=[1] * 8, max_new_tokens=4,
                          arrival_time=0.0))
    s.add_request(Request(rid=1, prompt=[1] * 8, max_new_tokens=4,
                          arrival_time=0.0))
    assert not s.has_capacity()
    assert s.queue_depths() == {"waiting": 2, "running": 0, "swapped": 0}
    h = Harness(s, 64, 256)
    h.run_iteration()
    assert s.queue_depths()["running"] > 0
    assert s.has_capacity()  # prefill drained the waitq
    # under gpu_only, host-resident rows are SWAPPED (not running) until
    # they come back — the vLLM state split
    s.cpu_runq.append(s.gpu_runq[0])
    del s.gpu_runq[0]
    s.cpu_runq[0].location = "cpu"
    assert s.queue_depths()["swapped"] == 1


def test_fastdecode_offloads_everything():
    s = make_scheduler("fastdecode")
    h = Harness(s, 64, 256)
    for i in range(6):
        s.add_request(Request(rid=i, prompt=[1] * 50, max_new_tokens=8,
                              arrival_time=float(i)))
    saw_decode = False
    for it in range(500):
        plan = h.run_iteration()
        if plan is None:
            break
        assert not plan.decode_gpu
        saw_decode = saw_decode or bool(plan.decode_cpu1)
    assert saw_decode


# ---------------------------------------------------------------------------
# zero-copy host serving: placement preference must never livelock
# ---------------------------------------------------------------------------


def test_host_preferred_placement(cfg=None):
    """A prefill whose longest cached prefix is host-resident is placed on
    the CPU queue first (zero-copy host serving), even with free HBM."""
    sched = make_scheduler()
    req = Request(rid=0, prompt=[2] * 32, max_new_tokens=4)
    req.cached_len = 16
    req.prefix_loc = "cpu"
    sched.add_request(req)
    plan = sched.plan(PoolView(PAGE, 64, 256))
    assert req in plan.prefill and req in plan.prefill_to_host


def test_host_preference_bounced_by_step5_falls_back_to_device():
    """Regression: a host-preferred prefill that step 5 (reduce prefill)
    bounces back to the waitq must fall back to DEVICE placement on the
    next plan — the place-then-drop cycle previously repeated forever,
    head-of-line-blocking the FIFO while HBM sat free."""
    sched = make_scheduler()
    # a permanently hot CPU queue: a long-KV host row + a maxed cpu_attn
    # scale makes cpu_demand dwarf the hideable window every iteration
    sched.perf.scale["cpu_attn"] = PerfModel.SCALE_MAX
    hot = Request(rid=0, prompt=[1] * 256, max_new_tokens=64)
    hot.state = RequestState.RUNNING
    hot.location = "cpu"
    hot.pages = list(range(17))
    sched.cpu_runq.append(hot)

    req = Request(rid=1, prompt=[2] * 32, max_new_tokens=4)
    req.cached_len = 16
    req.prefix_loc = "cpu"
    sched.add_request(req)

    plan1 = sched.plan(PoolView(PAGE, 64, 256))
    # step 3 host-placed it, step 5 dropped it back to the waitq
    assert req not in plan1.prefill
    assert sched.waitq and sched.waitq[0] is req
    plan2 = sched.plan(PoolView(PAGE, 64, 256))
    # the bounce disarmed the preference: admitted on the device
    assert req in plan2.prefill
    assert req not in plan2.prefill_to_host

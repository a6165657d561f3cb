"""ADAS-style multi-network taskset on the paper's machine.

The paper motivates its architecture with automated driving, where several
networks run concurrently at different rates on one shared-memory fabric.
This demo mixes:

  * an object detector   (YOLOv5s-flavored CNN)   @ 30 Hz
  * a lane-keeper        (small CNN)              @ 100 Hz
  * a speech interface   (LM decode step)         @ 10 Hz

and compiles them — one `repro.compile` call on the spec list — into ONE
static hyperperiod schedule for the single DMA channel + worker cores,
printing per-network WCET response bounds, the schedulability verdict,
the replay check that actual (faster) times never violate the bounds, and
a real inference through a member network's executable deployment. The
same taskset is then served through `repro.serve.Server`: admission-
controlled registration, submitted requests with per-ticket deadline
verdicts over several hyperperiods, and a save/load round-trip of the
whole serving configuration as one artifact bundle.

The second half is the robustness story: the same ADAS stack driven
through an injected overload burst (the low-criticality infotainment
network is shed at a hyperperiod boundary and hysteretically restored
once load recedes, while the safety-critical detector stays at zero
misses), then an atomic highway -> parking mode change that swaps the
whole taskset exactly at a hyperperiod boundary.

    PYTHONPATH=src python examples/adas_taskset.py
"""

import os

import numpy as np

import repro
from repro.core import cnn
from repro.core.compiled import supports_graph
from repro.core.lmgraph import lm_decode_graph
from repro.core.taskset import NetworkSpec, schedule_taskset
from repro.hw import scaled_paper_machine
from repro.models.config import ModelConfig
from repro.serve import (BreakerPolicy, FaultPlan, Mode, ModeNetwork,
                         OverloadPolicy, RetryPolicy, Server)


def speech_decoder_graph():
    """One decode step of a tiny speech-interface LM (2-layer stack kept
    small enough for the paper machine's 1 MiB scratchpads)."""
    cfg = ModelConfig(name="speech_lm", family="dense", num_layers=2,
                      d_model=256, num_heads=4, num_kv_heads=4, d_ff=512,
                      vocab_size=4096, act="gelu")
    return lm_decode_graph(cfg, batch=1, cache_len=128)


def main():
    hw = scaled_paper_machine(16)
    specs = [
        NetworkSpec("detector", cnn.yolov5s_backbone(h=64, w=64, width=0.25),
                    period_s=1 / 30),
        NetworkSpec("lane_keeper", cnn.small_cnn(48, 48), period_s=1 / 100),
        NetworkSpec("speech", speech_decoder_graph(), period_s=1 / 10),
    ]

    print("=" * 72)
    print("ADAS taskset: detector@30Hz + lane-keeper@100Hz + speech@10Hz")
    print(f"on {hw.name} ({hw.num_workers} cores, single DMA channel)")
    print("=" * 72)
    deploy = repro.compile(specs, hw, backend="numpy", num_cores=16)
    print(deploy.summary())
    assert deploy.schedulable, "demo taskset should fit the paper machine"

    compiled, report = deploy.taskset, deploy.report
    print()
    print("merged hyperperiod program: "
          f"{len(compiled.schedule.dma)} DMA transactions, "
          f"{len(compiled.schedule.compute)} compute slots, "
          f"{report.total_jobs} jobs")

    # compositionality at taskset level: replay every job at actual rates
    bounds = {n.name: n.response_bound_s for n in report.networks}
    schedule_taskset(compiled, hw, wcet=False)
    print("\nWCET response bounds vs actual-rate replay:")
    for spec in specs:
        actual = compiled.response_bound(spec.name)
        bound = bounds[spec.name]
        assert actual <= bound * (1 + 1e-9)
        print(f"  {spec.name:<12} replay {actual*1e3:7.3f} ms <= "
              f"bound {bound*1e3:7.3f} ms  "
              f"(tightness {actual/bound:.2f})")
    print("\nall networks meet their deadlines; bounds hold under replay")

    # members whose op kinds all have a lowering are executable deployments
    g = specs[1].graph
    x = np.random.default_rng(0).integers(
        -64, 64, tuple(g.tensors[g.inputs[0]].shape)).astype(np.int8)
    out = deploy.run("lane_keeper", x)
    print("lane_keeper logits: "
          f"{out[g.outputs[0]].ravel()[:6]}")

    # -- the serving front door: the same taskset behind repro.serve.Server --
    print()
    print("=" * 72)
    print("Serving the taskset: repro.serve.Server (admission + tickets)")
    print("=" * 72)
    srv = Server(hw, backend="numpy", num_cores=16)
    for spec in specs:
        # the speech decoder graph has no executable lowering: a stand-in
        # step_fn serves its requests while the bound covers the graph
        step_fn = (None if supports_graph(spec.graph)
                   else (lambda tok: np.int64(tok) + 1))
        v = srv.register(spec.name, spec.graph, spec.period_s,
                         step_fn=step_fn)
        print(f"  admitted {v.row()}")

    rng = np.random.default_rng(1)
    tickets = [srv.submit("lane_keeper",
                          rng.integers(-64, 64, (48, 48, 3)).astype(np.int8))
               for _ in range(6)]
    srv.run(hyperperiods=3)                     # release-order, sustained
    r = tickets[0].result()
    print(f"\nticket 0: latency {r.latency_s * 1e3:.3f} ms  "
          f"bound {r.response_bound_s * 1e3:.3f} ms  "
          f"deadline {'MET' if r.deadline_met else 'MISSED'}")
    print(srv.monitor.summary())

    # a whole serving configuration is one AOT artifact bundle; kept
    # under out/ so `python -m repro.analysis` can lint it afterwards
    os.makedirs("out", exist_ok=True)
    path = srv.save(os.path.join("out", "adas.bundle"))
    srv2 = Server.load(path)
    t1 = srv.submit("lane_keeper", x)
    t2 = srv2.submit("lane_keeper", x)
    srv.run(hyperperiods=1)
    srv2.run(hyperperiods=1)
    o1, o2 = t1.result().output, t2.result().output
    assert all(np.array_equal(o1[k], o2[k]) for k in o1)
    print("\nServer.save/load round-trip: bit-exact serving "
          f"({os.path.basename(path)})")

    degraded_ops_demo(hw)


def degraded_ops_demo(hw):
    """Overload shedding + atomic mode change, under injected faults.

    Highway mode: safety-critical detector @100Hz (criticality 2) next to
    a best-effort infotainment LM @20Hz (criticality 0). A burst of
    infotainment requests trips the hysteretic `OverloadPolicy`: the
    low-criticality network is shed at a hyperperiod boundary (its
    tickets resolve degraded — terminally, never hanging) and restored
    after consecutive calm boundaries. Then `switch_mode` swaps the whole
    taskset to parking mode exactly at a hyperperiod boundary. Throughout,
    a seeded `FaultPlan` injects failures into infotainment executor
    calls; bounded retries + a circuit breaker absorb them. The detector
    must come through all of it with zero deadline misses.
    """
    print()
    print("=" * 72)
    print("Degraded operation: overload shed/restore + highway->parking")
    print("=" * 72)
    srv = Server(hw, backend="numpy", num_cores=16,
                 queue_capacity=8, queue_policy="drop-oldest",
                 speed_ratio=1e9,           # pin: deadline checks are modeled
                 overload=OverloadPolicy(shed_queue_frac=0.5,
                                         restore_queue_frac=0.25,
                                         restore_hyperperiods=2))
    srv.register("detector", cnn.small_cnn(48, 48), period_s=1 / 100,
                 slots=2, criticality=2)
    srv.register("infotainment", speech_decoder_graph(), period_s=1 / 20,
                 criticality=0, step_fn=lambda tok: np.int64(tok) + 1)
    srv.enable_resilience(
        faults=FaultPlan(seed=11, fail_rate=0.3, timeout_rate=0.1,
                         networks=("infotainment",)),
        retry=RetryPolicy(max_retries=1),
        breaker=BreakerPolicy(threshold=3, cooldown_jobs=2))
    # the ACTIVE program's hyperperiod shrinks while infotainment is shed,
    # so drive load by modeled duration, not by active-program hyperperiods
    full_hp = srv.compiled.hyperperiod_s

    rng = np.random.default_rng(2)
    def frame(side):
        return rng.integers(-64, 64, (side, side, 3)).astype(np.int8)

    tickets = []

    # -- burst: 5 infotainment arrivals >= shed threshold (0.5 x 8) ----------
    tickets += [srv.submit("infotainment", np.int64(tok)) for tok in range(5)]
    tickets += [srv.submit("detector", frame(48)) for _ in range(2)]
    srv.run(duration_s=full_hp)
    assert srv.shed_networks == ["infotainment"], srv.shed_networks
    print(f"burst:   infotainment shed at the boundary "
          f"(sheds={srv.metrics['sheds']}, its tickets resolve degraded; "
          f"active bounds re-analyzed: {sorted(srv.report.response_bounds)})")

    # -- calm traffic: restore after 2 consecutive calm boundaries -----------
    for _ in range(3):
        tickets.append(srv.submit("detector", frame(48)))
        srv.run(duration_s=full_hp)
    assert srv.shed_networks == [], srv.shed_networks
    t = srv.submit("infotainment", np.int64(41))
    tickets.append(t)
    srv.run(duration_s=full_hp)
    print(f"calm:    infotainment restored (restores="
          f"{srv.metrics['restores']}); post-restore request -> "
          f"{t.status}" + (f", output {t.result().output}" if t.done else ""))

    # -- atomic mode change: highway -> parking at the boundary only ---------
    parking = Mode("parking", (
        ModeNetwork("detector", cnn.small_cnn(48, 48), period_s=1 / 50,
                    slots=2, criticality=2),
        ModeNetwork("park_assist", cnn.small_cnn(32, 32), period_s=1 / 50,
                    slots=2, criticality=1),
    ))
    tickets.append(srv.submit("detector", frame(48)))
    srv.step()                           # now mid-hyperperiod
    info2 = [srv.submit("infotainment", np.int64(7)) for _ in range(3)]
    tickets += info2
    report = srv.switch_mode(parking)    # admission-checked + compiled NOW
    assert report.schedulable and srv.mode_name is None   # staged, not applied
    print(f"staged:  parking mode admitted "
          f"({sorted(report.response_bounds)}); old schedule still active")
    srv.run(hyperperiods=1)              # rest of the old hyperperiod drains
    assert srv.mode_name is None         # ... still highway at the boundary
    srv.run(hyperperiods=1)              # first step crosses it: swap applies
    assert srv.mode_name == "parking", srv.mode_name
    dropped = sum(t.status == "dropped" for t in info2)
    print(f"switch:  applied at the hyperperiod boundary "
          f"(mode_switches={srv.metrics['mode_switches']}); departing "
          f"infotainment tickets: {dropped} dropped terminally")

    pa = srv.submit("park_assist", frame(32))
    tickets.append(pa)
    srv.run(hyperperiods=1)
    r = pa.result()
    print(f"parking: park_assist served  latency {r.latency_s*1e3:.3f} ms  "
          f"bound {r.response_bound_s*1e3:.3f} ms  "
          f"deadline {'MET' if r.deadline_met else 'MISSED'}")

    # the contract: every ticket terminal, safety-critical network clean
    assert all(t.terminal for t in tickets)
    assert srv.monitor.misses.get("detector", 0) == 0
    ev = srv.monitor.events
    print(f"\nevery ticket terminal ({len(tickets)}); detector misses 0; "
          f"injected faults absorbed "
          f"(retries={srv.metrics['retries']}, events={dict(ev)})")


if __name__ == "__main__":
    main()

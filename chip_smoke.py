#!/usr/bin/env python3
"""Chip smoke: qwen3-8b at its published widths, served through the gateway
on one TPU chip, with random weights drawn from a seed.

It drives the live path once: ``ClusterGateway`` (maestro policy, trained
predictor) -> ``NodeRuntime`` -> ``Engine`` -> ``KVArena`` -> the Pallas
``paged_attention`` kernel, and checks it:

- every stage of every job finishes exactly once, and tokens come out;
- every engine runs the Pallas kernels;
- the first-decode logits of two prompts through the Pallas paged path agree
  with the same engine on the jnp reference kernels (``LOGIT_TOL`` below);
- a second leg with chunked prefill (256-token chunks), an 8-token decode
  horizon and the prefix cache finishes every stage, and its last-prompt-
  token logits agree with the first leg's.

With ``--chips 4`` it runs only the path across chips instead: four nodes,
one per chip, over the default RTT clusters, against one node on one chip.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # four replicas, one per chip

It needs a TPU: where JAX finds none (``JAX_PLATFORMS=cpu``, say), it exits
non-zero and prints no result. Everything runs in this one process: it
starts no child, since a chip belongs to one process. The readings it prints
(bytes, compiles, wall seconds) are smoke readings, not speed metrics. On
success its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

MODEL = "qwen3-8b"
N_LAYERS = 24            # of 36; see DEPTH_CUT
DEPTH_CUT = ("depth cut 36 -> 24 layers: at 36 the bf16 weights are 15.3 "
             "GiB, more than a 16 GiB v5e holds beside the KV; at 24 they "
             "are 10.9 GiB")
S_MAX, MAX_SLOTS, PAGE = 2048, 8, 16
N_JOBS, PROMPT_CAP, GEN_CAP, VOCAB = 6, 512, 64, 151936
# monolithic prefill compiles once per distinct prompt length
PROMPT_LENGTHS = (128, 320, 512)
CHUNK, HORIZON = 256, 8
# the logit probes: two prompts of PROBE_LEN tokens sharing PROBE_SHARED
PROBE_LEN, PROBE_SHARED = 320, 256
SEED = 0
# bf16 logits: ||a - b||_2 / ||b||_2 over the padded vocabulary row. Two
# correct paths differ by bf16 rounding, which grows with depth (1.6e-2 at
# 12 layers of d_model 256 in a CPU rehearsal); a wrong page, mask or head
# mapping gives a difference of order 1.
LOGIT_TOL = 0.1


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[smoke] ok: {what}", flush=True)


# ---------------------------------------------------------------- building

def build_qwen(jax):
    """qwen3-8b at published widths, depth cut to fit one chip, and its
    host-tier parameters drawn from ``SEED`` on the host's CPU device."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.cluster import host_params
    cfg = dataclasses.replace(get_config(MODEL), n_layers=N_LAYERS)
    print(f"[smoke] model {MODEL}: d_model {cfg.d_model}, {cfg.n_heads} "
          f"heads, {cfg.n_kv_heads} KV heads, head_dim {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}, qk_norm {cfg.qk_norm}, "
          f"{jax.numpy.dtype(cfg.dtype).name}; {DEPTH_CUT}", flush=True)
    model = build_model(cfg)
    t0 = time.perf_counter()
    host = host_params(model, SEED)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(host))
    print(f"[smoke] host-tier weights: {nbytes / 2**30:.2f} GiB drawn in "
          f"{time.perf_counter() - t0:.1f} s wall (smoke reading)",
          flush=True)
    return model, host, nbytes


def make_jobs():
    """About six jobs of the generated trace, with real token prompts whose
    lengths are snapped to ``PROMPT_LENGTHS``."""
    from repro.data.tracegen import generate_trace
    from repro.serving.cluster import jobs_from_trace
    jobs = jobs_from_trace(generate_trace(N_JOBS, rate=1.5, seed=SEED),
                           vocab=VOCAB, prompt_cap=PROMPT_CAP,
                           gen_cap=GEN_CAP, seed=SEED)
    for j in jobs:
        for s in j.stages:
            n = next((L for L in PROMPT_LENGTHS if L >= len(s.tokens)),
                     PROMPT_LENGTHS[-1])
            s.tokens = (s.tokens * -(-n // len(s.tokens)))[:n]
    return jobs


def probe_prompts(jobs):
    """Two prompts made from served stages, each longer than one chunk; the
    second shares its first ``PROBE_SHARED`` tokens (whole pages) with the
    first, so the prefix cache has something to hit."""
    toks = [s.tokens for j in jobs for s in j.stages]
    a = (toks[0] * PROBE_LEN)[:PROBE_LEN]
    other = next(t for t in toks if t[:PAGE] != a[:PAGE])
    tail = PROBE_LEN - PROBE_SHARED
    return a, a[:PROBE_SHARED] + (other * tail)[:tail]


def node_spec(cluster_id, budget, **kw):
    from repro.serving.cluster import NodeSpec
    return NodeSpec(cluster_id, hbm_budget=budget, max_slots=MAX_SLOTS,
                    s_max=S_MAX, **kw)


# ----------------------------------------------------------------- serving

def serve(fleet, jobs, predictor, label):
    """Serve ``jobs`` through the gateway under the maestro policy; check
    that every stage finishes exactly once. Returns stage id -> tokens."""
    from repro.core.topology import DEFAULT_RTT
    from repro.serving.gateway import ClusterGateway, GatewayConfig
    gw = ClusterGateway(fleet, DEFAULT_RTT.copy(), predictor=predictor,
                        policy="maestro",
                        cfg=GatewayConfig(node_backend="inproc"))
    finishes = collections.Counter()
    outs = {}
    complete = gw._complete

    def counted(stage, model, req, now):
        finishes[stage.stage_id] += 1
        outs[stage.stage_id] = list(req.out)
        complete(stage, model, req, now)

    gw._complete = counted
    t0 = time.perf_counter()
    m = gw.run(jobs)
    wall = time.perf_counter() - t0
    n_stages = sum(len(j.stages) for j in jobs)
    print(f"[smoke] {label}: {m.finished_stages}/{n_stages} stages, "
          f"{m.generated_tokens} tokens, {wall:.1f} s wall, outcome "
          f"{m.run_outcome} (smoke readings)", flush=True)
    check(m.run_outcome == "completed" and m.dropped_jobs == 0
          and m.finished_jobs == len(jobs),
          f"{label}: all {len(jobs)} jobs finished, none dropped")
    check(len(finishes) == n_stages and set(finishes.values()) == {1},
          f"{label}: each of {n_stages} stages finished exactly once")
    check(m.truncated_stages == 0 and m.generated_tokens > 0
          and all(outs.values()),
          f"{label}: {m.generated_tokens} tokens, every stage produced some")
    backends = {e.kv_backend for n in fleet for e in n.engines.values()}
    check(backends == {"pallas"},
          f"{label}: every engine reports kv_backend 'pallas' ({backends})")
    return outs


def probe(jax, model, params, device, prompts, **engine_kw):
    """Serve ``prompts`` one after another on a standalone engine on
    ``device`` over ``params``; per prompt, the f32 logits rows its tokens
    were picked from: [last prompt token, first decode] (the horizon path
    keeps only the first)."""
    import numpy as np
    from repro.core.runtime.accounting import MemoryAccountant
    from repro.serving.engine import Engine, Request
    rows = []
    with jax.default_device(device):
        eng = Engine(model, params, MemoryAccountant(m_total=4e9, m_other=0),
                     max_slots=MAX_SLOTS, s_max=S_MAX, page_tokens=PAGE,
                     **engine_kw)
        for i, p in enumerate(prompts):
            req = Request(i, list(p), max_new=2, logits=[])
            eng.submit(req)
            eng.drain()
            rows.append([np.asarray(r, np.float32) for r in req.logits])
        eng.release_kv()
    return rows


def close(a, b, what):
    import numpy as np
    err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    same = int(np.argmax(a)) == int(np.argmax(b))
    print(f"[smoke] {what}: relative L2 {err:.3e} (tolerance {LOGIT_TOL}), "
          f"max |diff| {float(np.max(np.abs(a - b))):.3e}, same argmax "
          f"{same}", flush=True)
    check(np.all(np.isfinite(a)) and err <= LOGIT_TOL, what)


def leg(jax, model, zoo, host, budget, predictor, prompts, label, probes,
        **kw):
    """Serve the jobs on a one-node fleet (on the first chip), then run
    the logit probes on that node's weights. Returns (stage outputs, {probe
    name: rows}); the fleet and its device arrays go when it returns."""
    from repro.serving.cluster import ClusterSpec, build_fleet
    fleet = build_fleet(ClusterSpec(nodes=(node_spec(0, budget, **kw),),
                                    model_names=(MODEL,)),
                        zoo=zoo, host=host, backend="inproc")
    outs = serve(fleet, make_jobs(), predictor, label)
    node = fleet[0]
    eng = node.engines[MODEL]
    if kw:
        check(eng.chunk_tokens == CHUNK and eng.horizon == HORIZON
              and node.arena.prefix_index is not None,
              f"{label}: the engine runs chunked prefill, the horizon and "
              f"the prefix cache")
    params = node.device_params[MODEL]
    return outs, {name: probe(jax, model, params, node.device, prompts,
                              **pkw)
                  for name, pkw in probes.items()}


# ------------------------------------------------------------------- legs

def one_chip(jax, device, predictor):
    budget = float(device.memory_stats()["bytes_limit"])
    model, host, _ = build_qwen(jax)
    zoo, host = {MODEL: model}, {MODEL: host}
    prompts = probe_prompts(make_jobs())
    _, rows = leg(jax, model, zoo, host, budget, predictor, prompts,
                  "leg 1 (monolithic prefill)",
                  {"pallas": dict(kv_backend="pallas"),
                   "ref": dict(kv_backend="ref")})
    gc.collect()
    for i in range(2):
        close(rows["pallas"][i][1], rows["ref"][i][1],
              f"prompt {i}: first-decode logits, Pallas paged vs ref")
    kw = dict(prefill_chunk_tokens=CHUNK, decode_horizon=HORIZON,
              prefix_cache=True)
    _, rows2 = leg(jax, model, zoo, host, budget, predictor, prompts,
                   f"leg 2 (chunk {CHUNK}, horizon {HORIZON}, prefix cache)",
                   {"pallas": dict(kv_backend="pallas", **kw)}, **kw)
    gc.collect()
    for i in range(2):
        close(rows2["pallas"][i][0], rows["pallas"][i][0],
              f"prompt {i}: last-prompt-token logits, leg 2 vs leg 1")


def four_chips(jax, devices, predictor):
    """Four qwen3-8b nodes, one per chip, over the default RTT clusters,
    against one node on one chip: same jobs, same probe prompts."""
    from repro.serving.cluster import ClusterSpec, build_fleet
    budget = float(devices[0].memory_stats()["bytes_limit"])
    model, host, weight_bytes = build_qwen(jax)
    zoo, host = {MODEL: model}, {MODEL: host}
    prompts = probe_prompts(make_jobs())
    outs1, rows = leg(jax, model, zoo, host, budget, predictor, prompts,
                      "one node, one chip",
                      {"pallas": dict(kv_backend="pallas")})
    ref = rows["pallas"]
    gc.collect()

    fleet = build_fleet(ClusterSpec(nodes=tuple(node_spec(c, budget)
                                                for c in (0, 0, 1, 2)),
                                    model_names=(MODEL,)),
                        zoo=zoo, host=host, backend="inproc")
    check([n.device.id for n in fleet] == [d.id for d in devices[:4]],
          "node i sits on chip i")
    for n in fleet:
        n.activate(MODEL)          # each chip takes its copy of the weights
    outs4 = serve(fleet, make_jobs(), predictor, "four nodes, one per chip")
    same = sum(outs4[s] == outs1[s] for s in outs1)
    print(f"[smoke] {same}/{len(outs1)} stages generated the same tokens as "
          f"on one node", flush=True)
    for n in fleet:
        rows = probe(jax, model, n.device_params[MODEL], n.device, prompts,
                     kv_backend="pallas")
        for i in range(2):
            close(rows[i][0], ref[i][0], f"chip {n.device.id} prompt {i}: "
                  f"last-prompt-token logits vs one chip")
            close(rows[i][1], ref[i][1], f"chip {n.device.id} prompt {i}: "
                  f"first-decode logits vs one chip")
    for d in devices[:4]:
        peak = d.memory_stats()["peak_bytes_in_use"]
        print(f"[smoke] chip {d.id}: peak_bytes_in_use {peak} "
              f"({peak / weight_bytes:.2f} x one copy of the weights; smoke "
              f"reading)", flush=True)
        check(weight_bytes <= peak < 2 * weight_bytes,
              f"chip {d.id} held one copy of the weights")


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica path, one per chip")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repository's src/repro is not beside this "
              "script", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]
    # the host tier is drawn on the host's CPU device, so keep the CPU
    # platform enabled next to the TPU
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: found no TPU (JAX platform "
              f"{devices[0].platform!r}); it does not run on the CPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    dev = devices[0]
    print(f"[smoke] device {dev.platform} '{dev.device_kind}' x "
          f"{len(devices)}, bytes_limit {dev.memory_stats()['bytes_limit']}, "
          f"compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    try:
        from serve_multi_agent import train_predictor
        predictor = train_predictor()
        if args.chips == 4:
            four_chips(jax, devices, predictor)
        else:
            one_chip(jax, dev, predictor)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[smoke] peak_bytes_in_use {peak}, {len(compiles)} compiles in "
          f"{sum(compiles):.1f} s, "
          f"{time.perf_counter() - t0:.1f} s wall (smoke readings)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

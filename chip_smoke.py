#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the one model with a chip history (the dense 671M LM of
``bench.py``: vocab 32768, d_model 2048, 8 layers, 32 heads x 64, d_ff
8192, bf16 compute, T=1024; weights random from a seed):

- ``inventory``    a child process reports what JAX sees.  Anything but
                   ``tpu``, or a device kind with no sourced peak in
                   ``tracking/ledger.py:PEAK_FLOPS``, ends the script there.
- ``train-1chip``  ``kind: experiment`` -> ``lm_train`` on ``v5e-1``.
- ``serve-1chip``  ``kind: service`` -> ``lm_server`` -> ``ServingEngine``,
                   a handful of ``/generate`` requests, the normal stop API.
- ``train-4chip``  (>= 4 chips) ``v5e-4``: ``fsdp`` at T=1024, and
                   ``sp_ring`` + flash at T=8192 over ``{sequence: 4}``.
                   On fewer chips: ``skipped: <n> chips``, never ``ok``.

Every leg after ``inventory`` is a gang the ``Orchestrator`` spawns exactly
as ``polyaxon-tpu run`` does (spec -> compiler -> ``GangSpawner`` ->
``runtime.worker`` -> entrypoint), one after another, each gang exited —
chip released — before the next starts.  THIS process never imports jax: a
chip belongs to one process at a time.  Run state goes to a temp dir outside
the checkout; a small JSON summary and the workers' logs go to
``chiprun_out/chip_smoke/``.

Contract: exit 0 only if every leg passed, within 1200 s, compilation
included; the last stdout line is then
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
No accelerator, a failed or timed-out leg, any exception: non-zero exit and
no result line.  Rates printed here are information labelled with the
device, not metrics of record.

``--cpu-toy`` runs the same legs at a toy size on virtual CPU devices (an
explicit argument for debugging the script, never a fallback).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

#: The whole script's budget (the contract allows 1200 s).
DEADLINE_S = 1150.0

MODEL = {
    "vocab_size": 32768,
    "d_model": 2048,
    "n_layers": 8,
    "n_heads": 32,
    "head_dim": 64,
    "d_ff": 8192,
}
TOY_MODEL = {
    "vocab_size": 256,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "head_dim": 16,
    "d_ff": 128,
}

#: The largest of 20/16/8 that fits one 16 GB chip with lm_train's f32
#: AdamW (params + two f32 moments = 8.05 GB at rest) under
#: remat/save_attn: 20 fits (my chip run, PR 21).
TRAIN_BATCH = 20

#: |first-step loss(fsdp on 4 chips) - first-step loss(1 chip)|, same seed
#: and batch.  The weights are bit-identical (partitionable threefry), so
#: what differs is bf16 accumulation order across shards and the attention
#: path (GSPMD cannot partition a Mosaic call: fsdp runs XLA's dense
#: attention, one chip the flash kernel).  Measured 2.4e-5 on a loss of
#: 10.89 (my chip run, PR 21); 2e-3 leaves two orders of magnitude for
#: noise without hiding a wrong batch or a wrong shard.
FSDP_LOSS_TOL = 2e-3

_INVENTORY = (
    "import json, jax; d = jax.devices(); "
    "print('INVENTORY ' + json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))"
)


class LegFailed(Exception):
    pass


def _check(cond, what: str) -> None:
    if not cond:
        raise LegFailed(what)


class Smoke:
    def __init__(self, args) -> None:
        self.toy = bool(args.cpu_toy)
        self.batch = int(args.batch or (4 if self.toy else TRAIN_BATCH))
        self.legs = args.legs.split(",") if args.legs else None
        self.t0 = time.time()
        self.model = TOY_MODEL if self.toy else MODEL
        self.seq = 128 if self.toy else 1024
        self.long_seq = 512 if self.toy else 8192
        self.family = "cpu" if self.toy else "v5e"
        self.summary = {"toy": self.toy, "legs": {}}
        self.device = None
        self.orch = None
        self.live_runs = []
        self.first_loss_1chip = None

    # -- helpers ---------------------------------------------------------------
    def remaining(self) -> float:
        return DEADLINE_S - (time.time() - self.t0)

    def budget(self, want: float) -> float:
        left = self.remaining()
        _check(left > 30.0, f"out of time ({left:.0f}s left of {DEADLINE_S:.0f}s)")
        return min(want, left)

    def accelerator(self, chips: int) -> str:
        return f"{self.family}-{chips}" if not self.toy else (
            "cpu-1" if chips == 1 else "cpu"
        )

    def topology(self, chips: int, **extra) -> dict:
        topo = {"accelerator": self.accelerator(chips), **extra}
        if self.toy and chips != 1:
            topo.update(num_devices=chips, num_hosts=1)
        return topo

    def line(self, leg: str, status: str, **fields) -> None:
        dev = self.device or {}
        head = (
            f"leg={leg} {status} platform={dev.get('platform')} "
            f"kind={dev.get('kind')!r} devices={fields.pop('devices', dev.get('count'))}"
        )
        tail = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"{head} {tail}".rstrip(), flush=True)

    def save_logs(self, leg: str, run) -> None:
        logs = self.orch.layout.run_paths(run.uuid).logs
        for f in sorted(logs.glob("proc*.log")):
            data = f.read_bytes()[-200_000:]
            (OUT_DIR / f"{leg}.{f.name}").write_bytes(data)

    def run_logs(self, run_id: int) -> str:
        return "\n".join(
            r["line"] for r in self.orch.registry.get_logs(run_id)
        )

    def wait_done(self, run, timeout: float):
        """Pump until terminal or the bounded wait runs out (then stop the
        gang — TERM first, KILL after the grace — and fail)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.orch.pump(max_wait=0.2)
            cur = self.orch.get_run(run.id)
            if cur.is_done:
                return cur
        self.stop_run(run)
        raise LegFailed(f"run {run.id} still not done after {timeout:.0f}s")

    def stop_run(self, run) -> str:
        self.orch.stop_run(run.id)
        deadline = time.time() + 60.0
        while time.time() < deadline:
            self.orch.pump(max_wait=0.2)
            cur = self.orch.get_run(run.id)
            if cur.is_done:
                return cur.status
        return self.orch.get_run(run.id).status

    def submit(self, spec: dict, name: str):
        run = self.orch.submit(spec, name=name)
        self.live_runs.append(run)
        return run

    # -- legs ------------------------------------------------------------------
    def leg_inventory(self) -> None:
        env = dict(os.environ)
        if self.toy:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-c", _INVENTORY],
            env=env, capture_output=True, text=True,
            timeout=self.budget(180.0),
        )
        (OUT_DIR / "inventory.log").write_text(proc.stdout + proc.stderr)
        _check(proc.returncode == 0, f"inventory child exited {proc.returncode}: "
               + proc.stderr.strip()[-400:])
        found = [l for l in proc.stdout.splitlines() if l.startswith("INVENTORY ")]
        _check(found, "inventory child printed no INVENTORY line")
        dev = json.loads(found[-1][len("INVENTORY "):])
        self.device = dev
        from polyaxon_tpu.tracking.ledger import PEAK_FLOPS

        if not self.toy:
            _check(dev["platform"] == "tpu",
                   f"JAX found no TPU: platform {dev['platform']!r} ({dev['kind']})")
            _check(dev["kind"] in PEAK_FLOPS,
                   f"device kind {dev['kind']!r} has no sourced peak in PEAK_FLOPS")
        self.summary["device"] = dev
        self.summary["legs"]["inventory"] = {
            "ok": True, "wall_s": round(time.time() - t0, 2)
        }
        self.line("inventory", "ok", wall_s=f"{time.time() - t0:.1f}", compile_s="0.0")

    def _train(self, leg: str, chips: int, *, strategy: str = "ddp",
               mesh=None, seq=None, batch=None, extra=None, timeout=420.0):
        """One lm_train gang; returns the facts the leg checks."""
        seq = seq or self.seq
        batch = batch or self.batch
        t0 = time.time()
        spec = {
            "kind": "experiment",
            "run": {"entrypoint": "polyaxon_tpu.builtins.trainers:lm_train"},
            "declarations": {
                **self.model, "steps": 12, "batch": batch, "seq": seq,
                "lr": 3e-4, "remat": True, "remat_policy": "save_attn",
                **(extra or {}),
            },
            "environment": {
                "seed": 0,
                "topology": self.topology(
                    chips, strategy=strategy, **({"mesh": mesh} if mesh else {})
                ),
            },
        }
        run = self.submit(spec, leg)
        done = self.wait_done(run, self.budget(timeout))
        wall = time.time() - t0
        self.save_logs(leg, done)
        text = self.run_logs(run.id)
        _check(done.status == "succeeded",
               f"status {done.status!r}: " + text[-1500:])
        metrics = self.orch.registry.get_metrics(run.id)
        losses = [(m["step"], m["values"]["loss"]) for m in metrics
                  if "loss" in m["values"]]
        _check(len(losses) >= 2, f"fewer than two logged losses: {losses}")
        _check(all(l == l and abs(l) != float("inf") for _, l in losses),
               f"non-finite loss: {losses}")
        _check(losses[-1][1] < losses[0][1],
               f"loss did not fall on a fixed batch: {losses}")
        rows = self.orch.registry.get_utilization(run.id)
        _check(rows, "no ledger row")
        row = rows[-1]
        _check(row["devices"] == chips, f"ledger devices {row['devices']} != {chips}")
        place = [l for l in text.splitlines() if "lm_train placement: " in l]
        _check(place, "no placement report in the run log")
        place = json.loads(place[-1].split("lm_train placement: ", 1)[1])
        if not self.toy:
            _check(row["device_kind"].startswith("TPU"),
                   f"ledger device_kind {row['device_kind']!r}")
            _check(row["hbm_peak_bytes"] > 0, "ledger HBM peak is 0")
            _check(row["peak_flops_per_s"] > 0, "ledger peak FLOP/s is 0")
            _check(place["backend"] == "tpu", f"backend {place['backend']!r}")
            _check(all(v > 0 for v in place["bytes_in_use"].values())
                   and len(place["bytes_in_use"]) == chips,
                   f"bytes_in_use per device: {place['bytes_in_use']}")
        last = self.orch.get_run(run.id).last_metric or {}
        facts = {
            "ok": True,
            "status": done.status,
            "batch": batch,
            "seq": seq,
            "strategy": strategy,
            "wall_s": round(wall, 2),
            "compile_s": round(float(row["compile_s"]), 2),
            "aot_compile_s": round(float(last["aot_compile_s"]), 2),
            "cache_hits": row["attrs"].get("compile_cache_hits"),
            "cache_misses": row["attrs"].get("compile_cache_misses"),
            "losses": losses,
            "tokens_per_s_info": last.get("tokens_per_s"),
            "hbm_peak_bytes": row["hbm_peak_bytes"],
            "device_kind": row["device_kind"],
            "devices": row["devices"],
            "placement": place,
        }
        self.summary["legs"][leg] = facts
        return facts

    def _print_train(self, leg: str, f: dict) -> None:
        self.line(
            leg, "ok", devices=f["devices"],
            wall_s=f"{f['wall_s']:.1f}",
            # The step's own AOT compile (or its load from the cache); the
            # ledger's figure sums every jax compile-phase event, tracing
            # and lowering included, and barely moves on a cache hit.
            compile_s=f"{f['aot_compile_s']:.1f}",
            ledger_compile_s=f"{f['compile_s']:.1f}",
            cache_hits=f["cache_hits"], cache_misses=f["cache_misses"],
            batch=f["batch"], seq=f["seq"], strategy=f["strategy"],
            loss=f"{f['losses'][0][1]:.4f}->{f['losses'][-1][1]:.4f}",
            mosaic=",".join(f["placement"]["mosaic_kernels"]) or "none",
            info_tokens_per_s=(
                f"{f['tokens_per_s_info']:.0f}" if f["tokens_per_s_info"] else "n/a"
            ),
        )

    def leg_train_1chip(self) -> None:
        f = self._train("train-1chip", 1)
        if not self.toy:
            p = f["placement"]
            # Compiled — not interpreted, not dense: the step's HLO holds
            # the Mosaic custom calls of flash forward, dq and dkv.
            _check(p["mosaic_calls"] >= 3
                   and p["mosaic_kernels"] == ["flash_dkv", "flash_dq", "flash_fwd"],
                   f"Mosaic custom calls in the compiled step: "
                   f"{p['mosaic_calls']} {p['mosaic_kernels']}")
        self.first_loss_1chip = f["losses"][0][1]
        self._print_train("train-1chip", f)

    def leg_train_4chip(self) -> None:
        n = self.device["count"]
        if n < 4:
            self.summary["legs"]["train-4chip"] = {"skipped": f"{n} chips"}
            print(f"leg=train-4chip skipped: {n} chips", flush=True)
            return
        # (a) fsdp at the train-1chip shape: same seed, same batch.
        a = self._train("train-4chip-fsdp", 4, strategy="fsdp")
        pa = a["placement"]
        _check(len(set(pa["param_shards"].values())) == 4
               and len(pa["param_shards"]) == 4,
               f"fsdp: devices do not hold distinct shards of {pa['param']}: "
               f"{pa['param_shards']}")
        if self.first_loss_1chip is not None:
            diff = abs(a["losses"][0][1] - self.first_loss_1chip)
            a["first_loss_diff_vs_1chip"] = diff
            _check(diff <= FSDP_LOSS_TOL,
                   f"fsdp first-step loss {a['losses'][0][1]} vs one chip "
                   f"{self.first_loss_1chip}: |diff| {diff:.4f} > {FSDP_LOSS_TOL}")
        self._print_train("train-4chip-fsdp", a)
        # (b) the Pallas kernel inside shard_map with ppermute over ICI.
        b = self._train(
            "train-4chip-ring", 4, strategy="sp_ring", mesh={"sequence": 4},
            seq=self.long_seq, batch=2, extra={"attention_impl": "flash"},
        )
        # Under sp_ring nothing persistent is sharded (params and the input
        # batch are replicated; the sequence is split inside the step), so
        # the proof of four devices at work is in the compiled step: the
        # ring's collective-permutes, the Mosaic calls, and (checked in
        # _train) memory in use on every device.
        pb = b["placement"]
        _check(pb["collectives"].get("collective-permute", 0) > 0,
               f"sp_ring: no collective-permute in the compiled step: "
               f"{pb['collectives']}")
        if not self.toy:
            _check(pb["mosaic_calls"] >= 3
                   and pb["mosaic_kernels"] == ["flash_dkv", "flash_dq", "flash_fwd"],
                   f"sp_ring: Mosaic custom calls in the compiled step: "
                   f"{pb['mosaic_calls']} {pb['mosaic_kernels']}")
        self._print_train("train-4chip-ring", b)

    def leg_serve_1chip(self) -> None:
        leg = "serve-1chip"
        t0 = time.time()
        spec = {
            "kind": "service",
            "declarations": {**self.model, "seq": self.seq, "slots": 8},
            "environment": {"seed": 0, "topology": self.topology(1)},
        }
        run = self.submit(spec, leg)
        try:
            facts = self._drive_server(run, self.budget(600.0))
        except BaseException:
            self.stop_run(run)
            self.save_logs(leg, run)
            raise
        t_stop = time.time()
        status = self.stop_run(run)
        facts["stop_s"] = round(time.time() - t_stop, 2)
        self.save_logs(leg, run)
        _check(status == "stopped", f"after the stop API the run is {status!r}")
        from polyaxon_tpu.spawner.transport import LocalExecTransport

        for p in self.orch.registry.get_processes(run.id):
            if p.get("pid"):
                ref = LocalExecTransport().reattach(
                    "localhost", int(p["pid"]), OUT_DIR / "no-rc-file"
                )
                _check(ref.poll() is not None,
                       f"worker pid {p['pid']} still alive after stop")
        rows = self.orch.registry.get_utilization(run.id)
        row = rows[-1] if rows else {"attrs": {}}
        facts.update(
            ok=True, status=status, wall_s=round(time.time() - t0, 2),
            cache_hits=row["attrs"].get("compile_cache_hits"),
            cache_misses=row["attrs"].get("compile_cache_misses"),
        )
        self.summary["legs"][leg] = facts
        self.line(
            leg, "ok", devices=1, wall_s=f"{facts['wall_s']:.1f}",
            compile_s=f"{facts['ready_s']:.1f}",
            cache_hits=facts["cache_hits"], cache_misses=facts["cache_misses"],
            requests=facts["requests"], steady_state_compiles=0,
            stop_s=facts["stop_s"],
            info_decode_tokens_per_s=facts["decode_tokens_per_s_info"],
        )

    def _drive_server(self, run, timeout: float) -> dict:
        deadline = time.time() + timeout
        url = None
        health = None
        while time.time() < deadline:
            self.orch.pump(max_wait=0.2)
            cur = self.orch.get_run(run.id)
            _check(not cur.is_done,
                   f"server ended {cur.status!r} before ready: "
                   + self.run_logs(run.id)[-1500:])
            url = cur.service_url
            if not url:
                continue
            try:
                with urllib.request.urlopen(f"{url}/healthz", timeout=2) as r:
                    health = json.load(r)
            except urllib.error.HTTPError as e:
                health = json.load(e)
            except OSError:
                continue
            _check(health["state"] != "failed",
                   f"engine failed to start: {health.get('start_error')}")
            if health["state"] == "ready":
                break
        _check(health is not None and health.get("state") == "ready",
               f"server not ready after {timeout:.0f}s (last: {health})")

        vocab = self.model["vocab_size"]
        lengths = [5, self.seq // 8 + 5, self.seq // 4, self.seq // 2 + 3]
        max_new = 16
        prompts = [[(7 * i + 3 * n) % vocab for i in range(n)] for n in lengths]

        def generate(prompt_batch):
            req = urllib.request.Request(
                f"{url}/generate",
                data=json.dumps(
                    {"prompts": prompt_batch, "max_new_tokens": max_new}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=180) as r:
                return json.load(r)

        # Pump the control plane while requests are in flight (heartbeats,
        # report ingest) — the requests themselves ride worker threads.
        results = {}

        def call(key, prompt_batch):
            try:
                results[key] = generate(prompt_batch)
            except Exception as e:  # noqa: BLE001 - reported by the caller
                results[key] = e

        def run_calls(calls):
            threads = [threading.Thread(target=call, args=c) for c in calls]
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                self.orch.pump(max_wait=0.1)
                _check(time.time() < deadline, "requests outlived the leg's wait")
            for t in threads:
                t.join()

        run_calls([("first", [prompts[0]])])
        # Two HTTP requests at once, one of them holding two prompts.
        run_calls([("pair_a", [prompts[1], prompts[2]]), ("pair_b", [prompts[3]])])
        run_calls([("repeat", [prompts[0]])])
        for key, res in results.items():
            _check(not isinstance(res, Exception), f"/generate {key}: {res!r}")
            _check(all(len(t) == max_new for t in res["tokens"]),
                   f"/generate {key}: token counts "
                   f"{[len(t) for t in res['tokens']]} != {max_new}")
            _check(all(0 <= tok < vocab for t in res["tokens"] for tok in t),
                   f"/generate {key}: token id out of range")
        _check(results["first"]["tokens"] == results["repeat"]["tokens"],
               "repeated greedy request returned different tokens")
        with urllib.request.urlopen(f"{url}/v1/stats", timeout=10) as r:
            stats = json.load(r)
        _check(stats["steady_state_compiles"] == 0,
               f"steady_state_compiles = {stats['steady_state_compiles']} after traffic")
        _check(stats["requests_finished"] >= 5,
               f"requests_finished = {stats['requests_finished']}")
        return {
            "ready_s": stats["warmup"]["ready_s"],
            "warmup": stats["warmup"],
            "requests": stats["requests_finished"],
            "steady_state_compiles": stats["steady_state_compiles"],
            "decode_tokens_per_s_info": results["pair_a"]["decode_tokens_per_s"],
            "kv_pool_bytes": stats.get("kv_pool_bytes"),
        }

    # -- driver ----------------------------------------------------------------
    def main(self) -> int:
        if OUT_DIR.exists():
            shutil.rmtree(OUT_DIR)
        OUT_DIR.mkdir(parents=True)
        base_dir = tempfile.mkdtemp(prefix="chip_smoke_")
        ok = False
        try:
            self.leg_inventory()
            from polyaxon_tpu.orchestrator import Orchestrator

            self.orch = Orchestrator(
                base_dir, monitor_interval=0.5, heartbeat_interval=2.0,
                heartbeat_ttl=600.0,
            )
            # One inventory row for the host: admission then serialises
            # chip runs instead of letting two gangs race for a chip.
            self.orch.register_device(
                "smoke-host",
                "cpu" if self.toy else f"{self.family}-{self.device['count']}",
                self.device["count"],
            )
            legs = {
                "train-1chip": self.leg_train_1chip,
                "serve-1chip": self.leg_serve_1chip,
                "train-4chip": self.leg_train_4chip,
            }
            for name, fn in legs.items():
                if self.legs is not None and name not in self.legs:
                    self.summary["legs"][name] = {"skipped": "not selected"}
                    print(f"leg={name} skipped: not selected", flush=True)
                    continue
                fn()
            _check("jax" not in sys.modules,
                   "the smoke's parent process imported jax")
            ok = True
        except LegFailed as e:
            print(f"FAILED: {e}", file=sys.stderr, flush=True)
            self.summary["error"] = str(e)
        finally:
            self._cleanup(base_dir)
            self.summary["ok"] = ok
            self.summary["wall_s"] = round(time.time() - self.t0, 2)
            self.summary["claim"] = None
            (OUT_DIR / "summary.json").write_text(
                json.dumps(self.summary, indent=1)
            )
        if not ok:
            return 1
        dev = self.device
        result = {"ok": True, "device": {
            "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
        }}
        if self.toy:
            result["toy"] = True
        print(json.dumps(result), flush=True)
        return 0

    def _cleanup(self, base_dir: str) -> None:
        """Stop every process this script started, whatever happened."""
        if self.orch is not None:
            try:
                for run in self.live_runs:
                    if not self.orch.get_run(run.id).is_done:
                        self.stop_run(run)
            finally:
                self.orch.stop()
        shutil.rmtree(base_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-toy", action="store_true",
                    help="toy widths on virtual CPU devices (debugging the "
                         "script; explicit, never a fallback)")
    ap.add_argument("--legs", default="",
                    help="comma-separated subset of train-1chip,serve-1chip,"
                         "train-4chip (inventory always runs)")
    ap.add_argument("--batch", type=int, default=0,
                    help=f"train batch (default {TRAIN_BATCH})")
    args = ap.parse_args()
    if not (ROOT / "polyaxon_tpu" / "__init__.py").exists():
        print(f"chip_smoke.py: no polyaxon_tpu package beside {ROOT}/chip_smoke.py "
              "— run it from the root of the checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))

    return Smoke(args).main()


if __name__ == "__main__":
    sys.exit(main())

"""Multi-pod dry-run (charter deliverable e): lower + compile every
(architecture x input-shape) combination against the production meshes
and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multi-pod] [--step auto|train|prefill|decode|fed_round]
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --step fed_round --fed-framework kd
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json

The XLA_FLAGS line below MUST run before any other jax-importing code:
jax locks the device count at first backend init.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

from repro.configs.registry import ARCHS, get_config          # noqa: E402
from repro.configs.shapes import SHAPES, shape_supported, skip_reason  # noqa: E402
from repro.launch import steps as steps_mod                   # noqa: E402
from repro.launch.mesh import (activate_mesh, cost_analysis_dict,  # noqa: E402
                               make_production_mesh)
from repro.models import common                               # noqa: E402
from repro.roofline import collectives as coll_mod            # noqa: E402

GiB = 2**30

ASSIGNED = [a for a in ARCHS if not a.startswith("gpt2")]


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            step: str = "auto", remat: str = "full",
            scan_layers: bool = True, verbose: bool = True,
            parse_collectives: bool = True,
            fed_framework: str = "fedllm", kernel_policy: str = None,
            client_ranks=None, aggregation: str = "sync",
            dp_clip: float = 0.0, dp_noise_multiplier: float = 0.0,
            secure_agg: bool = False, backend: str = "spmd",
            shard_clients: bool = False, n_clients: int = None,
            population: str = None, cohort_size: int = None,
            robust_agg: str = "mean", faults: str = None) -> dict:
    from repro.configs.base import PrivacyConfig

    if step == "fed_round" and backend not in ("spmd", "cohort"):
        raise ValueError(
            "--step fed_round lowers the SPMD round program (the "
            "sequential backend is a python loop with no single-program "
            "artifact); use --backend spmd or cohort")
    # --population dirichlet:<alpha>:<n_virtual>: the cohort-streaming
    # scenario.  The compiled artifact is the per-cohort chunk program
    # (the host driver re-invokes it over the stream), so the stacked
    # client axis is clamped to one cohort — the virtual population
    # size only shows up in the cohort count.
    n_virtual = None
    pop_alpha = None
    if population:
        try:
            kind, alpha_s, nv = population.split(":")
            if kind != "dirichlet":
                raise ValueError(kind)
            pop_alpha, n_virtual = float(alpha_s), int(nv)
        except ValueError:
            raise ValueError(
                f"bad --population {population!r} (expected "
                "dirichlet:<alpha>:<n_virtual>, e.g. dirichlet:0.5:100000)")
        if not cohort_size:
            raise ValueError("--population requires --cohort-size (the "
                             "virtual fleet streams cohort by cohort)")
    if cohort_size:
        n_clients = cohort_size if n_clients is None \
            else min(n_clients, cohort_size)
    # --faults dropout:0.2,byzantine:2,...: fault injection is host-side
    # (faults/plan.py draws from the seed tree and corrupts payloads at
    # the upload seam), so it never changes the compiled round — the
    # record keeps the scenario; --robust-agg DOES change the program
    # (the closing client-axis reduction becomes the robust statistic).
    fault_cfg = None
    if faults:
        from repro.configs.base import FaultConfig
        keymap = {"dropout": ("dropout_rate", float),
                  "straggler": ("straggler_rate", float),
                  "delay": ("straggler_delay", int),
                  "byzantine": ("byzantine", int),
                  "mode": ("byzantine_mode", str),
                  "scale": ("byzantine_scale", float)}
        kw = {}
        try:
            for item in faults.split(","):
                k, v = item.split(":")
                field, cast = keymap[k]
                kw[field] = cast(v)
        except (ValueError, KeyError):
            raise ValueError(
                f"bad --faults {faults!r} (expected comma-separated "
                f"key:value with keys {sorted(keymap)}, e.g. "
                "dropout:0.2,byzantine:2)")
        fault_cfg = FaultConfig(**kw)
    cfg = get_config(arch)
    if kernel_policy:
        # thread ModelConfig.kernel_policy through the lowering path —
        # launch/steps traces every step under the config's policy scope
        cfg = dataclasses.replace(cfg, kernel_policy=kernel_policy)
    shape = SHAPES[shape_name]
    privacy = PrivacyConfig(dp_clip=dp_clip,
                            dp_noise_multiplier=dp_noise_multiplier,
                            secure_agg=secure_agg)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "step": shape.mode if step == "auto" else step,
           "kernel_policy": cfg.kernel_policy}
    if step == "fed_round":
        rec["fed_framework"] = fed_framework
        rec["backend"] = backend
        if population:
            rec["population"] = population
            rec["dirichlet_alpha"] = pop_alpha
            rec["n_virtual_clients"] = n_virtual
        if cohort_size:
            rec["cohort_size"] = cohort_size
            rec["cohort_count"] = -(-(n_virtual or n_clients
                                      or 2) // cohort_size)
        # async reuses the same per-bucket local-update programs — the
        # arrival schedule is host-side — so the compile artifact is the
        # sync one; the record keeps the axis visible in sweeps.
        rec["aggregation"] = aggregation
        if robust_agg != "mean":
            rec["robust_agg"] = robust_agg
        if fault_cfg is not None:
            rec["faults"] = faults
            rec["fault_config"] = dataclasses.asdict(fault_cfg)
        if client_ranks:
            rec["client_ranks"] = list(client_ranks)
        if privacy.enabled:
            # per-config privacy record: the knobs plus the secure-agg
            # setup bytes (host-side overlay — not part of the program).
            # The sync masking cohort is the whole client set, which for
            # the dry-run build is len(client_ranks) or the builder's
            # 2-client default.
            rec["dp_clip"] = dp_clip
            rec["dp_noise_multiplier"] = dp_noise_multiplier
            rec["secure_agg"] = secure_agg
            if secure_agg:
                from repro.privacy.secure_agg import key_exchange_bytes
                cohort = len(client_ranks) if client_ranks else 2
                up, down = key_exchange_bytes(cohort)
                rec["secagg_key_bytes_per_client"] = up + down

    # Heterogeneous client_ranks compile one stacked program per rank
    # bucket (core/round_program.SpmdExecutor runs exactly these
    # per-bucket programs).  Split buckets only contiguous equal-rank
    # runs — the shared server half is carried client-after-client — so
    # its program set comes from rank_segments, like the runtime's.
    builds = [("", {})]
    if step == "fed_round" and client_ranks:
        from repro.core import fed_spmd
        group = fed_spmd.rank_segments if fed_framework == "split" \
            else fed_spmd.rank_buckets
        sigs = []                     # distinct (rank, size) signatures —
        for rank, cis in group(list(client_ranks)):   # jit reuses repeats
            if (rank, len(cis)) not in sigs:
                sigs.append((rank, len(cis)))
        builds = [(f"rank{rank}x{n}", {"n_clients": n, "lora_rank": rank})
                  for rank, n in sigs]

    if step == "auto" and not shape_supported(cfg, shape):
        rec["status"] = "SKIP"
        rec["reason"] = skip_reason(cfg, shape)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    if step == "fed_round" and shard_clients:
        from repro.launch.mesh import client_axes, client_axis_size
        # default the client count to the client-axis extent so the
        # stacked axis shards 1:1 over the mesh's client axes
        if n_clients is None:
            n_clients = client_axis_size(mesh)
        rec["client_axis"] = list(client_axes(mesh))
        rec["shard_clients"] = True
    rec["status"] = "OK"
    programs = []
    with activate_mesh(mesh):
        for label, build_kw in builds:
            common.enable_shard_hints(True)
            try:
                t0 = time.time()
                if step == "fed_round":
                    fed_kw = dict(framework=fed_framework, privacy=privacy,
                                  shard_clients=shard_clients,
                                  robust_agg=robust_agg)
                    if n_clients is not None:
                        fed_kw["n_clients"] = n_clients
                    if cohort_size:
                        fed_kw["cohort_size"] = cohort_size
                    if backend == "cohort" and fed_framework == "fedllm":
                        # hierarchical a4 reduce: one edge per pod
                        from repro.launch.mesh import n_edges as mesh_edges
                        ne = mesh_edges(mesh)
                        if ne > 1:
                            fed_kw["n_edges"] = ne
                            rec["n_edges"] = ne
                    fed_kw.update(build_kw)
                    fn, args, shardings = steps_mod.build_fed_round_step(
                        cfg, shape, mesh, remat=remat, **fed_kw)
                else:
                    fn, args, shardings = steps_mod.build_step(
                        cfg, shape, mesh, scan_layers=scan_layers,
                        remat=remat)
                jitted = jax.jit(fn, in_shardings=shardings)
                lowered = jitted.lower(*args)
                t_low = time.time() - t0
                compiled = lowered.compile()
                t_comp = time.time() - t0 - t_low
            finally:
                common.enable_shard_hints(False)

            from repro.kernels import ops as kernel_ops
            if step == "fed_round" and privacy.dp_clip > 0 \
                    and fed_framework in ("fedllm", "kd") \
                    and kernel_ops.resolve(cfg.kernel_policy) == "pallas":
                # verify the DP machinery actually reached the jitted
                # round: under the pallas policy the fused clip kernel
                # must appear in the traced jaxpr by name.  (Split's
                # threat surface is the c2 activation clip+noise — jnp
                # row math inside split_step, no per-example grads — so
                # there is no clip kernel to find in its round.  The
                # extra trace only runs for this pallas gate; under xla
                # the kernel can never appear, so nothing to check.)
                txt = str(jax.make_jaxpr(fn)(*args))
                in_jaxpr = "dp_clip_mean_rows" in txt
                rec["dp_clip_kernel_in_jaxpr"] = in_jaxpr
                if not in_jaxpr:
                    raise RuntimeError(
                        "--dp-clip with --kernel-policy pallas but the "
                        "dp_clip_mean_rows kernel is not in the traced "
                        "jaxpr — the DP-SGD path did not reach the "
                        "jitted round")

            if step == "fed_round" and robust_agg in ("median",
                                                      "trimmed_mean"):
                # verify the robust statistic reached the jitted round:
                # both median and trimmed mean lower through a sort on
                # the stacked client axis, which plain FedAvg never emits
                txt = str(jax.make_jaxpr(fn)(*args))
                in_jaxpr = "sort" in txt
                rec["robust_sort_in_jaxpr"] = in_jaxpr
                if not in_jaxpr:
                    raise RuntimeError(
                        f"--robust-agg {robust_agg} but no sort appears "
                        "in the traced jaxpr — the robust reduction did "
                        "not reach the jitted round")

            if step == "fed_round" and shard_clients:
                # acceptance gate: the client-axis NamedSharding must be
                # visible in the lowered program (GSPMD spells it
                # 'devices=[C,...'; the Shardy partitioner spells it
                # '#sdy.sharding<..{"<axis>"}..>')
                from repro.launch.mesh import (client_axes,
                                               client_axis_size)
                txt = lowered.as_text()
                size = client_axis_size(mesh)
                ax = client_axes(mesh)[0]
                in_hlo = (f"devices=[{size}," in txt) or (
                    "sdy.sharding" in txt and f'{{"{ax}"}}' in txt)
                rec["client_axis_sharding_in_hlo"] = in_hlo
                if not in_hlo:
                    raise RuntimeError(
                        "--shard-clients but no client-axis sharding is "
                        "visible in the lowered program — the stacked "
                        "client dimension did not reach the mesh's "
                        f"{ax!r} axis")

            ma = compiled.memory_analysis()
            ca = cost_analysis_dict(compiled)
            prog = {
                "lower_s": round(t_low, 2),
                "compile_s": round(t_comp, 2),
                "arg_gib_per_dev": round(ma.argument_size_in_bytes / GiB, 3),
                "temp_gib_per_dev": round(ma.temp_size_in_bytes / GiB, 3),
                "out_gib_per_dev": round(ma.output_size_in_bytes / GiB, 3),
                "hlo_flops": ca.get("flops", 0.0),
                "hlo_bytes": ca.get("bytes accessed", 0.0),
            }
            if parse_collectives:
                try:
                    cb = coll_mod.collective_bytes(compiled.as_text())
                    prog["collective_bytes"] = cb
                    prog["collective_total"] = sum(cb.values())
                except Exception as e:                 # pragma: no cover
                    prog["collective_error"] = str(e)
            if label:
                prog["bucket"] = label
            programs.append(prog)

    if len(programs) == 1:
        # the common single-program case keeps the original flat schema
        # (incl. the per-kind collective_bytes dict / collective_error)
        rec.update(programs[0])
    else:
        # roll per-bucket programs up into the flat record the sweep
        # tooling reads: summed time/flops, peak per-device memory
        for k in ("lower_s", "compile_s", "hlo_flops", "hlo_bytes"):
            rec[k] = round(sum(p[k] for p in programs), 2)
        for k in ("arg_gib_per_dev", "temp_gib_per_dev", "out_gib_per_dev"):
            rec[k] = max(p[k] for p in programs)
        if any("collective_total" in p for p in programs):
            cb = {}
            for p in programs:
                for kind, nbytes in p.get("collective_bytes", {}).items():
                    cb[kind] = cb.get(kind, 0) + nbytes
            rec["collective_bytes"] = cb
            rec["collective_total"] = sum(p.get("collective_total", 0)
                                          for p in programs)
        errs = [f"{p.get('bucket', i)}: {p['collective_error']}"
                for i, p in enumerate(programs) if "collective_error" in p]
        if errs:                                       # pragma: no cover
            rec["collective_error"] = "; ".join(errs)
        rec["bucket_programs"] = programs
    if cohort_size and step == "fed_round":
        # the per-cohort peak: one chunk program's whole footprint —
        # under cohort streaming this bounds the round regardless of
        # the virtual population size
        rec["cohort_peak_gib_per_dev"] = round(
            rec.get("arg_gib_per_dev", 0.0)
            + rec.get("temp_gib_per_dev", 0.0)
            + rec.get("out_gib_per_dev", 0.0), 3)
    if verbose:
        print(f"[{rec['status']}] {arch} x {shape_name} ({rec['mesh']}, "
              f"{rec['step']}): compile={rec.get('compile_s', '-')}s "
              f"args={rec.get('arg_gib_per_dev', '-')}GiB "
              f"temp={rec.get('temp_gib_per_dev', '-')}GiB "
              f"coll={rec.get('collective_total', 0)/1e9:.2f}GB"
              + (f" buckets={len(programs)}" if len(programs) > 1 else ""))
        if cohort_size and step == "fed_round":
            print(f"    cohorts: {rec.get('cohort_count')} x "
                  f"{cohort_size} clients"
                  + (f" of {n_virtual} virtual" if n_virtual else "")
                  + f", per-cohort peak "
                  f"{rec['cohort_peak_gib_per_dev']}GiB/dev")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (assigned arch x shape), both meshes")
    ap.add_argument("--step", default="auto",
                    choices=["auto", "train", "prefill", "decode",
                             "fed_round"])
    ap.add_argument("--fed-framework", default="fedllm",
                    choices=["fedllm", "kd", "split"],
                    help="which paper framework --step fed_round compiles")
    ap.add_argument("--backend", default="spmd",
                    choices=["sequential", "spmd", "cohort"],
                    help="round-engine execution backend for --step "
                         "fed_round; spmd compiles the whole stacked "
                         "round, cohort compiles the per-cohort chunk "
                         "program the streaming driver re-invokes")
    ap.add_argument("--shard-clients", action="store_true",
                    help="shard the stacked client axis of --step "
                         "fed_round over the mesh's client axes "
                         "(launch/mesh.client_axes) with explicit "
                         "NamedShardings, and verify the sharding is "
                         "visible in the lowered program")
    ap.add_argument("--n-clients", type=int, default=None,
                    help="client count for --step fed_round (default 2, "
                         "or the client-axis extent with "
                         "--shard-clients); with --cohort-size this is "
                         "an alias clamped to one cohort")
    ap.add_argument("--population", default=None,
                    help="virtual client population for --step fed_round "
                         "as dirichlet:<alpha>:<n_virtual>, e.g. "
                         "dirichlet:0.5:100000 — the cohort-streaming "
                         "scenario (requires --cohort-size; the record "
                         "gets cohort_count and the per-cohort peak "
                         "memory)")
    ap.add_argument("--cohort-size", type=int, default=None,
                    help="clients per streamed cohort for --step "
                         "fed_round: the compiled chunk program stacks "
                         "exactly one cohort, whatever the population "
                         "size")
    ap.add_argument("--kernel-policy", default=None,
                    choices=["xla", "pallas", "auto"],
                    help="override ModelConfig.kernel_policy for the "
                         "lowered step (pallas = fused fwd+bwd kernels)")
    ap.add_argument("--client-ranks", default=None,
                    help="comma-separated per-client LoRA ranks for "
                         "--step fed_round, e.g. 4,8,8,16; compiles one "
                         "stacked program per rank bucket")
    ap.add_argument("--aggregation", default="sync",
                    choices=["sync", "async"],
                    help="aggregation schedule axis to record; async "
                         "reuses the per-bucket local-update programs "
                         "(arrival scheduling is host-side)")
    ap.add_argument("--dp-clip", type=float, default=0.0,
                    help="per-example L2 clip for --step fed_round: the "
                         "fused DP-SGD clip kernel enters the jitted "
                         "round (verified in the traced jaxpr under "
                         "--kernel-policy pallas)")
    ap.add_argument("--dp-noise-multiplier", type=float, default=0.0,
                    help="Gaussian noise multiplier sigma (payload noise "
                         "stddev = sigma * clip); adds the per-client "
                         "noise-key inputs to the lowered round")
    ap.add_argument("--robust-agg", default="mean",
                    choices=["mean", "median", "trimmed_mean", "norm_clip"],
                    help="Byzantine-robust closing reduction for --step "
                         "fed_round; median/trimmed_mean are verified to "
                         "reach the traced jaxpr (they lower via sort)")
    ap.add_argument("--faults", default=None,
                    help="seeded fault-injection scenario to record, as "
                         "comma-separated key:value — e.g. "
                         "dropout:0.2,byzantine:2,mode:sign_flip "
                         "(keys: dropout, straggler, delay, byzantine, "
                         "mode, scale); host-side, does not change the "
                         "compiled program")
    ap.add_argument("--secure-agg", action="store_true",
                    help="record the simulated secure-aggregation "
                         "overlay (host-side masking; key-exchange "
                         "bytes in the record)")
    ap.add_argument("--remat", default="full", choices=["none", "full"])
    ap.add_argument("--no-scan", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args()

    records = []
    if args.all:
        for arch in ASSIGNED:
            for shape_name in SHAPES:
                for mp in (False, True):
                    records.append(run_one(arch, shape_name, mp,
                                           remat=args.remat,
                                           scan_layers=not args.no_scan))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        ranks = tuple(int(r) for r in args.client_ranks.split(",")) \
            if args.client_ranks else None
        meshes = (False, True) if args.both_meshes else (args.multi_pod,)
        for mp in meshes:
            records.append(run_one(args.arch, args.shape, mp,
                                   step=args.step, remat=args.remat,
                                   scan_layers=not args.no_scan,
                                   fed_framework=args.fed_framework,
                                   kernel_policy=args.kernel_policy,
                                   client_ranks=ranks,
                                   aggregation=args.aggregation,
                                   dp_clip=args.dp_clip,
                                   dp_noise_multiplier=(
                                       args.dp_noise_multiplier),
                                   secure_agg=args.secure_agg,
                                   backend=args.backend,
                                   shard_clients=args.shard_clients,
                                   n_clients=args.n_clients,
                                   population=args.population,
                                   cohort_size=args.cohort_size,
                                   robust_agg=args.robust_agg,
                                   faults=args.faults))

    ok = sum(r["status"] == "OK" for r in records)
    skip = sum(r["status"] == "SKIP" for r in records)
    print(f"\n{ok} OK, {skip} SKIP(policy), {len(records)-ok-skip} FAIL "
          f"of {len(records)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out}")
    if len(records) - ok - skip:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

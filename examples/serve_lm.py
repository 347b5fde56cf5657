"""Batched serving example: prefill a batch of prompts then decode with
the KV/SSM cache; reports tokens/s (CPU-scale model).

    PYTHONPATH=src python examples/serve_lm.py --arch yi-6b
    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-2.7b   # SSM cache

With ``--plan-mesh`` the example closes the paper's §V-B loop for
serving: ``plan_serving`` sweeps decode-step splits through the PALM
simulator for ``--hardware``, the suggested ``(data, model)`` mesh is
built via ``launch.mesh.make_serving_mesh`` (on forced host devices for
the CPU dry-run), and generation runs under that sharding:

    PYTHONPATH=src python examples/serve_lm.py --plan-mesh --hardware tpu_v5e_2x2
"""

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=48)
    ap.add_argument("--plan-mesh", action="store_true",
                    help="pick the (data, model) mesh with plan_serving and "
                         "shard the decode loop over it")
    ap.add_argument("--hardware", default="tpu_v5e_2x2",
                    help="hardware preset plan_serving simulates "
                         "(--plan-mesh only)")
    args = ap.parse_args()

    if args.plan_mesh:
        # the split covers every device of the simulated hardware; force
        # that many host devices before jax initializes its backend
        from repro.api import resolve_hardware   # jax-free import
        n = resolve_hardware(args.hardware).num_devices
        flag = f"--xla_force_host_platform_device_count={n}"
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.train import scale_arch
    from repro.models import RunCfg, init_params
    from repro.serving import greedy_generate, plan_serving

    arch = scale_arch(get_config(args.arch), "small")
    if arch.embeds_input:
        raise SystemExit(f"{arch.name} takes precomputed embeddings; "
                         "use an LM arch for this example")
    cfg = RunCfg(q_chunk=0, remat=False)

    mesh = None
    if args.plan_mesh:
        # before any JAX device use: plan_serving may fork simulator
        # workers, and a process whose backend is up holds the chip
        mesh_axes, report = plan_serving(
            arch, hardware=args.hardware, batch=args.batch,
            context_len=args.prompt_len + args.new_tokens)
        best = report.best
        print(f"plan_serving on {args.hardware}: mesh {mesh_axes} "
              f"({best.throughput:.1f} simulated decode steps/s, "
              f"{report.num_candidates} splits ranked)")
        mesh = make_serving_mesh(mesh_axes)

    params = init_params(arch, jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, arch.vocab)

    t0 = time.time()
    out = greedy_generate(arch, params, prompts, args.new_tokens, cfg, mesh=mesh)
    dt = time.time() - t0
    total_new = args.batch * args.new_tokens
    dev = jax.devices()[0]
    used = len(jax.devices()) if mesh is not None else 1
    print(f"{arch.name}: generated {out.shape} in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s on {used} x {dev.platform} "
          f"{dev.device_kind}, batch={args.batch})")
    print("first sequence:", out[0][:16].tolist())


if __name__ == "__main__":
    main()

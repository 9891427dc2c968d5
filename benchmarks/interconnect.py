"""E2 (paper C3): switchless mesh-torus vs switched NoC, at both scales.

Edge scale: first-order energy/latency from the CGRA model.
Pod scale: lowered-HLO comparison of the torus ring schedule
(collective_permute chain) vs XLA's default all-gather for the same
tensor-parallel GEMM, compiled in this process over every device it has:
the chips of a TPU host, or host CPU devices forced before the process
starts (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import re

from repro.core.cgra import CGRAConfig, simulate_transformer_layer


def pod_schedule() -> list[str]:
    """Collective-op counts of both schedules' compiled HLO."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import torus
    from repro.launch.mesh import make_mesh

    n = jax.device_count()
    if n < 2:
        return [f"not run: the ring needs >= 2 devices, this process has {n} "
                f"(on CPU start it with XLA_FLAGS="
                f"--xla_force_host_platform_device_count=8)"]
    mesh = make_mesh((n,), ("model",))
    T, D, F = 1024, 512, 2048
    x = jax.ShapeDtypeStruct((T, D), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((D, F), jnp.bfloat16)
    row, col = P("model", None), P(None, "model")

    ring = shard_map(lambda xs, ws: torus.ring_allgather_matmul(xs, ws),
                     mesh=mesh, in_specs=(row, col), out_specs=col)
    t_ring = jax.jit(ring).lower(x, w).compile().as_text()
    xla_default = jax.jit(  # x token-sharded -> XLA all-gathers
        jnp.matmul,
        in_shardings=(NamedSharding(mesh, row), NamedSharding(mesh, col)),
        out_shardings=NamedSharding(mesh, col))
    t_xla = xla_default.lower(x, w).compile().as_text()

    def stats(txt):
        return {k: len(re.findall(k, txt))
                for k in ("all-gather", "collective-permute", "all-reduce")}
    return [f"devices: {n} x {jax.devices()[0].device_kind}",
            f"ring {stats(t_ring)}", f"xla  {stats(t_xla)}",
            "derived: the torus schedule issues only neighbor "
            "collective-permutes (overlappable per-step), zero all-gathers"]


def run() -> list[str]:
    out = ["# E2 interconnect — edge scale (CGRA model, BERT-tiny layer, seq 128)"]
    out.append("interconnect,cycles,energy_uJ,power_mW,hop_energy_share")
    for name, cfg in (("switchless_torus", CGRAConfig()),
                      ("switched_noc", CGRAConfig(switched_noc=True))):
        tot, _ = simulate_transformer_layer(cfg, 256, 4, 64, 1024, seq=128)
        e_link = cfg.e_hop_word + (cfg.e_router_word if cfg.switched_noc else 0)
        hop_pj = tot.hops_words * e_link
        out.append(f"{name},{tot.cycles},{tot.energy_pj/1e6:.2f},"
                   f"{tot.power_mw:.3f},{hop_pj/tot.energy_pj:.3f}")
    t = simulate_transformer_layer(CGRAConfig(), 256, 4, 64, 1024, seq=128)[0]
    s = simulate_transformer_layer(CGRAConfig(switched_noc=True), 256, 4, 64,
                                   1024, seq=128)[0]
    out.append(f"derived: switchless saves {100*(1 - t.energy_pj/s.energy_pj):.1f}% "
               f"energy, {100*(1 - t.cycles/s.cycles):.2f}% latency (first-order)")

    out.append("")
    out.append("# E2 pod scale — HLO collective schedule, TP GEMM over every "
               "device")
    out.extend(pod_schedule())
    return out


if __name__ == "__main__":
    print("\n".join(run()))

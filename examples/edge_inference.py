"""The paper's scenario end to end: int8 (packed-data) transformer inference
through the CGRA block-GEMM path, validated against the fp32 reference and
costed on the 4x4 PE / 4x2 MOB array.

The whole model — q/k/v/o projections, MLP and LM head — runs through the
quantized GEMM stack (``quant="w8a8"`` + ``model.quantize_params``), not
just a single demo projection; ``kernel_mode="interpret"`` additionally
executes the exact Pallas kernel math on CPU.  The final section serves the
int8 model through the paged continuous-batching engine (``EngineConfig``),
the deployment shape the paper's accelerator targets.

    PYTHONPATH=src python examples/edge_inference.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core.cgra import CGRAConfig, simulate_transformer_layer
from repro.models import model as M
from repro.serving import Engine, EngineConfig, bytes_tokenizer_encode


def main():
    cfg = get_config("cgra-edge")
    params = M.init(cfg, jax.random.PRNGKey(0))
    B, S = 1, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    # fp32 reference logits
    hidden, _, _ = M.forward_hidden(cfg, params, {"tokens": tokens}, mode="train")
    logits_ref = M.lm_logits(cfg, params, hidden)

    # full w8a8 forward: weights int8-quantized once, every dense projection
    # and the LM head served through the packed int8 GEMM with fused dequant
    cfg_q = cfg.with_(quant="w8a8")
    params_q = M.quantize_params(cfg_q, params)
    hidden_q, _, _ = M.forward_hidden(cfg_q, params_q, {"tokens": tokens},
                                      mode="train")
    logits_q = M.lm_logits(cfg_q, params_q, hidden_q)
    rel = np.abs(np.asarray(logits_q) - np.asarray(logits_ref)) / (
        np.abs(np.asarray(logits_ref)) + 1.0)
    agree = float(np.mean(np.argmax(np.asarray(logits_q), -1)
                          == np.argmax(np.asarray(logits_ref), -1)))
    print(f"w8a8 full model: median rel err {np.median(rel):.4f}, "
          f"argmax agreement {agree:.3f}")

    # same quantized model through the Pallas kernels (interpret mode = the
    # exact kernel math, executed on CPU)
    cfg_qi = cfg_q.with_(kernel_mode="interpret")
    hidden_qi, _, _ = M.forward_hidden(cfg_qi, params_q, {"tokens": tokens},
                                       mode="train")
    logits_qi = M.lm_logits(cfg_qi, params_q, hidden_qi)
    dk = float(np.max(np.abs(np.asarray(logits_qi) - np.asarray(logits_q))))
    print(f"w8a8 Pallas-interpret vs jnp-int8 reference: max |dlogits| {dk:.2e}")

    # energy/latency budget on the paper's array
    cgra = CGRAConfig()
    tot, reps = simulate_transformer_layer(cgra, cfg.d_model, cfg.num_heads,
                                           cfg.head_dim, cfg.d_ff, seq=S)
    print(f"CGRA per-layer: {tot.time_us/1e3:.2f} ms, {tot.energy_pj/1e6:.1f} uJ, "
          f"{tot.power_mw:.2f} mW, PE util {tot.pe_utilization:.2f}")
    print(f"full {cfg.num_layers}-layer forward: "
          f"{cfg.num_layers*tot.time_us/1e3:.1f} ms @ ~{tot.power_mw:.1f} mW "
          f"-> edge-deployable (paper's ultra-low-power class)")
    for name, r in list(reps.items())[:3]:
        print(f"  {name:8s} cycles={r.cycles:8d} AI={r.arithmetic_intensity:5.1f} "
              f"util={r.pe_utilization:.2f}")

    # edge serving: the same int8 model behind the paged engine — requests
    # share KV pages for common prompt prefixes via the radix cache
    eng = Engine(cfg, params, EngineConfig(
        max_len=64, max_batch=2, page_size=16, quant="w8a8"))
    common = "edge transformer inference: "
    prompts = [bytes_tokenizer_encode(common + tail, cfg.vocab_size)
               for tail in ("keyword spotting", "wake word")]
    out, stats = eng.generate(prompts, max_new=8)
    print(f"served {len(out)} requests ({stats.tokens_out} tokens, "
          f"{stats.tokens_per_s:.1f} tok/s of compiled calls, "
          f"prefix_hit={eng.prefix_hit_rate:.0%}, "
          f"pages_used={eng.pool.num_used}/{eng.pool.n_pages - 1})")


if __name__ == "__main__":
    main()

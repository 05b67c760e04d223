"""Sampling/inference through the service on server-held trained weights.

Reference parity: examples/GPT2/predict_fns.py + models/gpt2/sample.py —
`sample_sequence` with temperature/top-k runs on the estimator's trained
weights; nothing is fetched to the client. Here: train a few steps over
RPC, then `compile_generate`/`generate` ship ONE decode program (static
KV cache + lax.scan over tokens, greedy or multinomial — typed-PRNG-key
jaxprs cross the wire) that reads the server's variable store.

    python examples/GPT2/generate.py --local --config test --steps 3 \
        --max_new_tokens 16 --temperature 0.8 --top_k 40
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..", "..")))

import argparse
import os
import signal

import jax
import optax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--config", default="test")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--prompt_len", type=int, default=8)
    ap.add_argument("--max_new_tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top_k", type=int, default=0)
    ap.add_argument("--greedy", action="store_true")
    args = ap.parse_args()

    from tepdist_tpu.client.session import TepdistSession
    from tepdist_tpu.models import gpt2, sampling
    from tepdist_tpu.rpc.local_server import (
        pin_client_to_cpu,
        server_platform,
        spawn_local_server,
    )

    # One process per chip: the client stays on the CPU, the server it
    # spawns is told to own the chip.
    pin_client_to_cpu()
    proc = None
    if args.local:
        proc, port = spawn_local_server(server_platform())
        address = f"127.0.0.1:{port}"
    else:
        address = (f"{os.environ.get('SERVER_IP', '127.0.0.1')}:"
                   f"{os.environ.get('SERVER_PORT', '2222')}")

    cfg = gpt2.CONFIGS[args.config]
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = gpt2.fake_batch(cfg, args.batch, args.seq)
    tx = optax.adam(1e-3)

    def step(params, opt_state, tokens):
        l, g = jax.value_and_grad(
            lambda p: gpt2.loss_fn(p, tokens, cfg))(params)
        u, opt_state = tx.update(g, opt_state, params)
        return l, optax.apply_updates(params, u), opt_state

    try:
        sess = TepdistSession(address)
        sess.client.wait_ready(timeout=120)
        sess.compile_train_step(step, params, tx.init(params), tokens)
        for i in range(args.steps):
            print(f"step {i}: loss={sess.run(tokens):.4f}")

        prompt = gpt2.fake_batch(cfg, 2, args.prompt_len + 1)[:,
                                                              :args.prompt_len]

        def gen_fn(p, prompt):
            return sampling.sample(
                p, prompt, cfg, max_new_tokens=args.max_new_tokens,
                temperature=args.temperature, top_k=args.top_k,
                greedy=args.greedy)

        sess.compile_generate(gen_fn, params, prompt)
        out = sess.generate(prompt)
        for row in jax.device_get(out):
            print("generated:", " ".join(str(int(t)) for t in row))
        sess.close()
    finally:
        if proc is not None:
            proc.send_signal(signal.SIGKILL)
            proc.wait()


if __name__ == "__main__":
    main()

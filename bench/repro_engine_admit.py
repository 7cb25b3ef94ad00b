"""Shows the serving engine's admission writing a prefill cache to the wrong place.

    JAX_PLATFORMS=cpu python bench/repro_engine_admit.py

On the CPU, at reduced qwen2-0.5b widths (2 layers, so 2 scanned periods,
float32), 4 decode slots: one 6-token prompt is admitted to slot 0 by
``Engine._admit``.  The caches are laid out (period, batch, position, ...),
and the admission writes ``c.at[slot].set(n[0])``: ``slot`` indexes the
period axis, and the request's period-0 cache is copied into every batch
row.  The script prints the positions each (period, row) of the cache holds
and the gap between the engine's next-token logits for slot 0 and a direct
``lm.prefill`` then ``lm.decode_step`` of the same prompt.  A sound engine
holds positions 0-5 in row 0 of both periods, nothing elsewhere, and shows
a gap at rounding level.  This defect keeps serving cells out of the
benchmark.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "src"))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.core.events import EventLog
    from repro.models import lm
    from repro.serving.engine import Engine, ServeConfig

    cfg = reduced(get_config("qwen2-0.5b"), layers=2)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    slots, max_seq, prompt = 4, 32, [5, 17, 42, 7, 99, 3]
    eng = Engine(cfg, params, ServeConfig(max_batch=slots, max_seq=max_seq), log=EventLog())
    eng.submit(prompt, max_new=4)
    eng._admit()
    pos = np.asarray(eng.caches["blocks"]["pos0"]["mixer"]["pos_ids"])
    for p in range(pos.shape[0]):
        for b in range(pos.shape[1]):
            held = pos[p, b][pos[p, b] >= 0].tolist()
            print(f"period {p} row {b}: positions {held}")

    first = eng.active[0].out[0]
    tokens = np.zeros(slots, np.int32)
    tokens[0] = first
    got, _ = eng._decode(params, jnp.asarray(tokens), jnp.asarray(eng.cur_pos), eng.caches)
    _, caches = lm.prefill(params, cfg, jnp.asarray(prompt, jnp.int32)[None], max_seq=max_seq)
    want, _ = lm.decode_step(params, cfg, jnp.asarray([first], jnp.int32),
                             jnp.asarray([len(prompt)], jnp.int32), caches)
    gap = float(jnp.max(jnp.abs(got[0] - want[0])))
    print(f"next-token logits, engine slot 0 vs direct prefill + decode: "
          f"max gap {gap:.6g}, largest logit {float(jnp.max(jnp.abs(want[0]))):.6g}")


if __name__ == "__main__":
    main()

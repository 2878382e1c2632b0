#!/usr/bin/env python
"""The witness of a refactor that must move nothing.  A JSON line a (family
of ``tests/test_chunk_head.py``, body, platform): sha256 of the text the
family's ``toy_decoder`` lowers to for the FIRST chunk, a CONTINUATION chunk a
page in and the decode step, on ``cpu`` as it stands and for ``tpu`` with
``use_pallas`` true (the kernels, no chip).  A line a family: sha256 over the
parameter tree's (path, shape, dtype) and, for the FAMILYs named (or all), the
leaves' bytes of ``init(key(0))``.  ``JAX_PLATFORMS=cpu python3
tools/serve_body_hashes.py [FAMILY ...]`` (1.7 min) in the parent's checkout
and in the change's; then ``diff``."""
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a kernel's payload names its ops' places in the source: as paths in the
# checkout and without the calls on the way down, or two checkouts differ
jax.config.update("jax_hlo_source_file_canonicalization_regex",
                  re.escape(ROOT))
jax.config.update("jax_include_full_tracebacks_in_locations", False)
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]
import test_chunk_head as toys  # noqa: E402
from dtf_tpu.serve import decode as sd  # noqa: E402


def sha(data) -> str:
    return hashlib.sha256(str(data).encode()).hexdigest()


def z(*shape, dtype=jnp.int32):
    return jnp.zeros(shape, dtype)


def bodies(dec, c, platform):
    """body -> the lowered text, for ``platform``."""
    n, m, f32 = dec.num_slots, dec.pages_per_slot, jnp.float32
    cache = jax.eval_shape(dec.fresh_cache)
    def chunk(start, first):
        window = (None if dec._kernel_attn
                  else (int(dec.table_index(start)) + c) // dec.page_size)
        return dec._chunk.trace(
            dec.params, cache, z(1, c), z(1, m), z(), z(dtype=f32),
            sd.position_key(0, 0), z() + start, window, first)
    decode = dec._decode.trace(
        dec.params, cache, z(n, 1), z(n), z(n, m), z(n, dtype=f32),
        sd._seed_row_keys(z(n, dtype=jnp.uint32), z(n)))
    return {k: t.lower(lowering_platforms=(platform,)).as_text()
            for k, t in (("chunk_first", chunk(0, True)), ("decode", decode),
                         ("chunk", chunk(dec.page_size, False)))}


if __name__ == "__main__":
    for family in toys.FAMILIES:
        dec, c = toys.toy_decoder(family, {"bf16": "bfloat16"}.get(
            toys._serving_cells()[family].config["dtype"], "float32"))
        kernels = sd.Decoder(
            dec.model.clone(use_pallas=True), dec.params,
            num_slots=dec.num_slots, max_seq_len=dec.max_seq_len,
            kv_page_size=dec.page_size, kv_pool_pages=dec.pool_pages)
        for platform, d in (("cpu", dec), ("tpu", kernels)):
            for body, text in bodies(d, c, platform).items():
                print(json.dumps({"family": family, "body": body, "platform":
                                  platform, "sha256": sha(text)}), flush=True)
        init = dec.model.clone(decode=False, use_pallas=False).init
        at = (jax.random.key(0), z(1, dec.page_size))
        flat = jax.tree_util.tree_leaves_with_path
        line = {"family": family, "params": sha([
            (jax.tree_util.keystr(p), s.shape, s.dtype)
            for p, s in flat(jax.eval_shape(init, *at)["params"])])}
        if family in (sys.argv[1:] or toys.FAMILIES):
            line["params_bytes"] = sha([np.asarray(v).tobytes().hex() for _, v
                                        in flat(jax.jit(init)(*at)["params"])])
        print(json.dumps(line), flush=True)

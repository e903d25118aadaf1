"""Serving launcher: batched requests through the lease-coherent server.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --requests 8

The server obtains every prefix-KV lease from the array-native coherence
fabric (--tsu-shards TSU shards; mesh-placed on devices via
``ShardedArrayFabric`` when more than one device is visible) via ONE
batched probe per serve call — the same backend (and the same `core.state`
transition rules) the trainer and benchmarks use.
"""
import argparse
import json

import jax
import numpy as np

from repro import configs as cfgs
from repro.coherence.fabric import FabricConfig, default_fabric
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model
from repro.runtime.server import Request, Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(cfgs.ARCHS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tsu-shards", type=int, default=4)
    ap.add_argument("--rd-lease", type=int, default=8)
    ap.add_argument("--wr-lease", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = cfgs.SMOKE[args.arch]            # serving demo runs the smoke cfg
    params = init_model(cfg, jax.random.PRNGKey(0))
    # mesh-placed TSU shards when this host has >1 device (DESIGN.md §8)
    fabric = default_fabric(FabricConfig(n_shards=args.tsu_shards,
                                         rd_lease=args.rd_lease,
                                         wr_lease=args.wr_lease))
    if getattr(fabric, "mesh", None) is not None:
        print(f"fabric mesh: {fabric.mesh} "
              f"({args.tsu_shards} shards on "
              f"{fabric.mesh.devices.size} devices)")
    srv = Server(cfg, params, batch_size=args.batch,
                 max_len=args.prompt_len + args.max_new + 8, fabric=fabric)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        # half the requests share a prompt -> exercises the lease cache
        seed = i % max(args.requests // 2, 1)
        prompt = np.random.default_rng(seed).integers(
            2, cfg.vocab, args.prompt_len).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=args.max_new))
    # two waves: wave 1 prefills under one batched probe + one batched
    # write-through; wave 2's identical prefixes ride the live leases
    out = srv.serve(reqs[:len(reqs) // 2])
    out.update(srv.serve(reqs[len(reqs) // 2:]))
    for rid in sorted(out):
        print(f"req {rid}: {list(out[rid])}")
    print("lease-cache stats:", srv.cache_stats)
    print("fabric stats:", json.dumps(srv.fabric_stats))


if __name__ == "__main__":
    main()

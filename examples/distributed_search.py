"""DaM-sharded distributed retrieval on a multi-device mesh (fake devices on
CPU): the paper's Fig. 12 mapping as a shard_map program, reached through the
unified ``Index.searcher(backend="sharded")`` call.

  PYTHONPATH=src python examples/distributed_search.py   # 8 simulated devices
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def main():
    import jax

    from repro.core import graph as gmod
    from repro.data.synthetic import make_dataset
    from repro.index import Index, IndexSpec, SearchParams

    db = make_dataset("unit")
    idx = Index.build(db, IndexSpec.for_db(db, m=8, dfloat_recall_target=None))
    n_shards = 4
    mesh = jax.make_mesh((2, n_shards), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    print(f"mesh: {mesh.devices.shape} (data x model); DB {db.n}x{db.dim}")

    owner = gmod.map_owners(db.n, n_shards, "shuffle")
    dam = gmod.build_dam(idx.graph.base_adjacency, owner, n_shards)
    print(f"DaM: {n_shards} shards, partition width {dam.max_part_width()} "
          f"(full lists M=8) — vector+list co-location per shard")

    run = idx.searcher("sharded", SearchParams(ef=48, k=10, use_dfloat=False),
                       mesh=mesh)
    res = run(db.queries)
    print(f"sharded search recall@10 = {res.recall(db.gt, 10):.4f} "
          f"over {len(db.queries)} queries")
    print("per-hop wire traffic: ef x shards x 8B (ids+dists) — vector payloads "
          "never cross shards (DaM)")


if __name__ == "__main__":
    main()

"""Worker process for the 2-process ``jax.distributed`` test.

Spawned by ``test_distributed.py`` (never collected by pytest itself):

    python distributed_worker.py <process_id> <coordinator_port>

Each worker brings up 2 virtual CPU devices, joins the 2-process world
(4-device global mesh), and exercises the real multi-host branches of
``Fabric`` — the analog of the reference's 2-process Gloo CI
(reference tests/test_algos/test_algos.py:16-52).
"""

import os
import sys


def main() -> None:
    process_id = int(sys.argv[1])
    port = sys.argv[2]

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")

    from sheeprl_tpu.fabric import Fabric, init_distributed

    # 1. world bring-up through the real entry (must precede any backend use)
    assert init_distributed(f"127.0.0.1:{port}", 2, process_id) is True
    assert jax.process_count() == 2
    assert jax.process_index() == process_id

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    # 2. Fabric sees the *world* mesh: 2 processes x 2 local devices
    fabric = Fabric(devices="auto", accelerator="cpu")
    assert fabric.world_size == 4, fabric.world_size
    assert len(fabric.local_devices) == 2
    assert fabric.is_global_zero == (process_id == 0)

    # 3. a jitted global reduction over the world mesh (XLA inserts the
    # cross-process psum from the shardings)
    local = np.full((2, 3), process_id + 1, np.float32)  # rows differ per rank
    garr = multihost_utils.host_local_array_to_global_array(
        local, fabric.mesh, P(fabric.data_axis)
    )
    out = jax.jit(
        lambda x: jnp.sum(x), out_shardings=NamedSharding(fabric.mesh, P())
    )(garr)
    total = float(np.asarray(jax.device_get(out.addressable_data(0))))
    assert total == 18.0, total  # 2*3*1 + 2*3*2

    # 4. host-side all_gather: every process contributes its own rows
    gathered = fabric.all_gather({"x": np.array([process_id, process_id + 10.0])})
    np.testing.assert_array_equal(gathered["x"], [[0.0, 10.0], [1.0, 11.0]])

    # 5. broadcast: rank-0 data reaches everyone
    payload = np.array([42.0, 7.0]) if process_id == 0 else np.zeros(2)
    got = fabric.broadcast({"p": payload})
    np.testing.assert_array_equal(got["p"], [42.0, 7.0])

    # 6. checkpoint round trip across the 2-process world: EVERY rank calls
    # fabric.save (Orbax's save runs its own cross-process sync — gating the
    # call to rank 0 deadlocks at save_start; only the primary host writes
    # bytes), both ranks restore, and the restored tree must be
    # bitwise-identical to the original on BOTH ranks (multi-host
    # checkpointing was untested before this)
    import tempfile

    state = {
        "params": {
            "w": np.arange(12, dtype=np.float32).reshape(3, 4) * (1.0 + 1e-7),
            "b": np.array([1.5, -2.25], np.float32),
        },
        "update": np.int64(7),
    }
    ckpt_dir = os.path.join(
        tempfile.gettempdir(), f"sheeprl_tpu_dist_ckpt_{port}", "ckpt"
    )
    fabric.save(ckpt_dir, state)
    # the non-writer must see a COMPLETE checkpoint immediately post-barrier
    restored = fabric.load(ckpt_dir)
    np.testing.assert_array_equal(restored["params"]["w"], state["params"]["w"])
    np.testing.assert_array_equal(restored["params"]["b"], state["params"]["b"])
    assert int(restored["update"]) == 7
    fabric.barrier("post-restore")
    if process_id == 0:
        import shutil

        shutil.rmtree(os.path.dirname(ckpt_dir), ignore_errors=True)

    # 7. barrier completes
    fabric.barrier("test-end")
    print(f"WORKER{process_id} PASS", flush=True)


if __name__ == "__main__":
    main()

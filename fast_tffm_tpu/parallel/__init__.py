from fast_tffm_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS,
    ROW_AXIS,
    check_batch_divides,
    make_mesh,
    pad_vocab,
    replicated,
    table_sharding,
)
from fast_tffm_tpu.parallel.train_step import (  # noqa: F401
    WireGlobalConverter,
    init_sharded_state,
    local_mesh_devices,
    make_global_batch,
    make_global_superbatch,
    make_replicator,
    make_sharded_predict_step,
    make_sharded_train_step,
    pack_sharded_on_device,
    packed_shard_meta,
    unpack_sharded_to_logical,
    unpack_sharded_on_device,
)

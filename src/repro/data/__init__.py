from repro.data.synthetic import (make_asr_data, make_cifar_like,  # noqa: F401
                                  make_lm_data)
from repro.data.partition import partition_iid, partition_noniid_shards  # noqa: F401
from repro.data.pipeline import (ClientSampler, DeviceClientStore,  # noqa: F401
                                 draw_indices)

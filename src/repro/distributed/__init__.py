"""Distribution layer for the HTAP mesh plane: island/replicated placement
rules (`sharding`) and the process-global island-mesh context (`context`)
installed by `HTAPSession` and consumed by `core.backend.MeshBackend`."""

from repro.distributed.context import (clear_island_mesh,  # noqa: F401
                                       current_island_mesh,
                                       install_island_mesh, island_mesh,
                                       release_island_mesh)
from repro.distributed.sharding import (ISLAND_AXIS,  # noqa: F401
                                        island_sharding, island_spec,
                                        place_shard_arrays,
                                        replicated_sharding, replicated_spec)

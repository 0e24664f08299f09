"""``keye_sparse``'s dense products that the family credits are
``mellum_moe``'s: the head, a layer's fused q/k/v (grouped k/v heads) and
output products, its router.  The experts' products are the ``expert_matmul``
class's; the indexer's three projections run forward only and are credited
nowhere (``families/keye_sparse.py``)."""

from benchmark.dense_products.mellum_moe import products  # noqa: F401

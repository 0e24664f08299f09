"""The dense products of a model family, one file a family: found by the
``family`` key of a configuration file, as ``families/<family>.py`` is
(``dense_groups.products``), so a later family brings its own file and
nothing that is here changes.  A file exposes

  products(config)  {group: [(in, out) widths of each dense product a token
                    passes]}

where a group is the name of the ``model.*`` scope its ops carry without the
prefix (``fedml_tpu/obs/scopes.py``: ``head`` is ``model.head``).  The
groups' FLOPs have to sum to the family's own
``fwd_flops_per_unit(config)["matmul"]``, or ``dense_groups`` refuses the
file.  A family without a file has no group rooflines and the rest of its
metrics."""

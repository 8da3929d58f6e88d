"""One module per ``model.arch`` of a configuration, named after the
arch with ``-`` replaced by ``_`` and loaded by path
(``bench.lib.harness.model_module``).  Each defines:

    dims(cfg)                     the numbers of the program's
                                  ModelConfig the benchmark uses
    make_weights(dims, key)       the benchmark's weights, on the device
    MODES                         reference precision first, then controls
    rank_scores(dims, w, tokens, n_fed, incr, items, mode)
                                  the plain reference of one request
    served_prefix(store, uid, cached)
                                  (tokens, n_fed) the served path computed
    prefill_work(dims, lens), rank_work(dims, prefix_lens, n_incr,
                                  n_items, cached)
                                  (FLOPs, bytes) of one launch

Nothing else in ``bench/`` knows the model.
"""

"""The benchmark of petastorm_tpu: ``python3 -m perfbench.run --workload ...``.

Everything the yardstick needs lives in this directory: traffic and store
generation, the trace reduction, the table of peaks, the operation counts,
each configuration's plain reference and the comparison that decides
``correct``. From the program it takes only the system under test
(``make_tensor_reader -> JaxLoader -> make_train_step``) and its counters.
"""

"""Data generators: ``<generator>.py`` for each ``config["data"]
["generator"]``, found by its name (``bench.generators.make_data``). A
generator module has ``make(seed, p)`` -> padded client arrays, and where
it can stream a population, ``virtual(seed, p, n_clients)`` -> a lazy
one (``bench.generators`` says what each returns). Shared helpers are in
``bench.generators``."""

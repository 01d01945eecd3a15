"""Model FLOPs and least HBM bytes of one federated round, from the
configuration and traffic shapes; one module per model family
(``bench/flops/<family>.py``, each with ``round_work(c, traffic)``)."""

"""One directory per model family: ``benchmark/families/<family>/``, found by the
``family`` key of a configuration file (``harness/manifest.py``), the way a loop driver is
found by a mix's ``kind`` and a reader by its metric's name. Nothing outside this
directory names a family or imports a model of the system under test.

A family is a package that gives, under these fixed names, what the drivers ask of it
(a family that only serves leaves the training names out, and the other way round):

every driver
    ``SIZE_KEYS``: the keys of a configuration file that size the model; they become the
    cell's ``sizes``. ``vocab_size`` is the one key every family has (the traffic
    generator draws token ids from it); a value may be a list, as a layer pattern is.
    ``build_model(config, deterministic)``, ``to_program_params(weights)``,
    ``from_program_params(params)``, ``check_param_tree(model, params)``: the glue to the
    system under test; this is the one place that imports its models.
    ``seed_key(seed)``, ``build_weights(sizes, key, dtype)``, ``make_weights(sizes, seed,
    dtype)``: the benchmark's own weights, on the device, in one jitted call.

``open_loop`` (``harness/loops/_serving.py``)
    ``warm_up_prompt_lengths(sizes, shortest, longest)``: prompt lengths whose warm-up
    requests compile every program that prompts between the two bounds can reach.
    ``live_cache_entries(sizes, prompt_tokens, new_tokens)``: the cache entries a request
    holds after ``new_tokens`` tokens (what a decode step has to read).
    ``TICK_PROGRAM``: the tick program's name in the device trace.
    ``check_served(weights, sizes, served, limits, checks, controls)``: the comparison
    that decides ``correct`` for served output; ``served`` is a seeded sample of
    ``(prompt, tokens)``, ``limits`` the cell's. ``harness/check.py`` holds the usual
    one (``served_token_deficits``: token deficits under the reference's logits, which
    needs the family's ``score_served``); a family may compare something else.

``train`` (``harness/loops/train.py``)
    ``make_program_train_step(model, tx, sizes)``: the program's own step.
    ``row_tokens(sizes)``: ``(tokens fed, tokens trained)`` of one row.
    ``make_train_step(sizes, optimizer, rows_per_block, precision)``, ``leaf_norms(tree)``:
    the plain float32 reference's step and its leaves' norms.
"""

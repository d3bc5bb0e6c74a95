"""Graph families, one module each: ``generate(params) -> (src, dst)``.

``params`` is the ``graph`` object of a configuration file; the result
is deterministic in it (its ``seed`` included). Vertex ids are int32 in
``[0, params["n"])``; every edge is a distinct unordered pair with
``src != dst``.
"""

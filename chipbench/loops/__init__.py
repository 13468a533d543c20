"""Window loops, one module per mix ``loop``; the harness finds each by
name (``chipbench.loops.<loop>``) and calls its ``warm`` and ``run``."""

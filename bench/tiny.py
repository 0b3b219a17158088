"""Tiny sizes for running the harness on the CPU in tests: the same code
paths as a cell, with widths and traffic a test run can hold."""

CONFIG = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=2, vocab_size=128)

ENGINE = {"slots": 4, "page_tokens": 16, "pool_pages": 128,
          "token_budget": 128, "chunk_tokens": 64, "max_len": 256}


MIX = {"loop": "closed", "size_seed": 0, "engine": ENGINE, "clients": 4,
       "set_size": 64, "check": {"rows": 6},
       "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 120},
       "output": {"median": 60, "sigma": 0.4, "min": 8, "max": 120}}

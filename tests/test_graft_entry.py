"""The graft entry contract (``__graft_entry__.py``): the entry's function
compiles, and the dry run shards it over eight virtual chips. Beside
``tests/test_models.py`` and not in it: a file is what tier-1's
``--dist loadfile`` schedules."""

import jax


class TestGraftEntry:
    def test_entry_compiles(self, hvd_flat):
        import sys
        sys.path.insert(0, "/root/repo")
        import __graft_entry__ as ge

        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (8, 1000)

    def test_dryrun_multichip(self):
        import sys
        sys.path.insert(0, "/root/repo")
        import __graft_entry__ as ge

        ge.dryrun_multichip(8)

"""The generator of a deployment whose dashboards name their hosts by a
pattern (``fleet-1m-wildcard``): ``gen.py``'s fleet, line for line, and
one key more that no series carries, ``host~pattern``, which exists
for the traffic generator's draw alone (``traffic.py`` can only put
``data.tag_name(tag, i)`` values into a filter): its ``i``-th value is
the ``i``-th host pattern of the deployment, in the two forms of
OpenTSDB's filter documentation (``wildcard(web*)``,
``wildcard(*mysite.com)``): one ``*``, at an end.

Host names are ``h`` and seven digits. With ``series`` = 10**n hosts
and ``k`` = min(4, n) the patterns are

- a prefix, ``h<7 - (n - k) digits>*``: the 10**(n - k) consecutive
  hosts of one block, 10**k blocks;
- a suffix, ``*<k digits>``: every 10**k-th host, 10**k residues;

prefixes first. Every pattern selects exactly 10**(n - k) hosts, the
same for every seed (the seed sets which pattern a request draws,
``traffic.py``): at 1,000,000 series ``h00000*`` .. ``h09999*`` and
``*0000`` .. ``*9999``, 20,000 patterns of 100 hosts; at 10,000 series
20,000 patterns of one host. A series count that is no power of ten
would give suffixes of two sizes and is refused.
"""

import gen

PATTERN_KEY = "host~pattern"    # ``~`` is in no tag key a TSD accepts


class Data(gen.Data):
    def __init__(self, spec: dict):
        super().__init__(spec)
        n = len(str(self.series)) - 1
        if self.series != 10 ** n:
            raise ValueError(
                f"{self.series} series: the patterns select the same "
                f"number of hosts only over a power of ten")
        self.suffix_digits = min(4, n)
        self.prefix_digits = 7 - (n - self.suffix_digits)
        self.hosts_per_pattern = 10 ** (n - self.suffix_digits)
        self.prefixes = self.series // self.hosts_per_pattern

    def tag_count(self, tagk: str) -> int:
        if tagk == PATTERN_KEY:
            return self.prefixes + 10 ** self.suffix_digits
        return super().tag_count(tagk)

    def tag_name(self, tagk: str, i: int) -> str:
        if tagk != PATTERN_KEY:
            return super().tag_name(tagk, i)
        if i < self.prefixes:
            return f"h{i:0{self.prefix_digits}d}*"
        return f"*{i - self.prefixes:0{self.suffix_digits}d}"


generate = gen.generate

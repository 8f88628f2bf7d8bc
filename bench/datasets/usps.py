"""USPS addresses (paper Table 1): person name + street + city + state
records with nickname, state and street-suffix rules.

The record generator is the program's ``make_usps``
(``repro/data/strings.py``); the rules are the benchmark's own.  The
program's generator makes 55 rules where the paper's USPS set has 341,
so this copy makes 341 of the same kinds, and widens the record's state
and street-type vocabularies to the rules' dictionary sides, so that
every rule applies to some record:

- nicknames of the generator's 50 first names (first and last names
  both come from that list);
- the two-letter postal codes of the 50 states and DC;
- the generator's short forms of street words (``map`` -> ``maple``);
- street suffixes with the Postal Service's standard abbreviation and
  the common variants it lists beside it (USPS Publication 28,
  Appendix C1).

They are taken in that order, each list in its own order, and the first
``RULES`` distinct pairs are kept.
"""

from __future__ import annotations

import numpy as np

from bench.datasets import StringDataset, _scores

#: the paper's USPS rule count (Table 1)
RULES = 341

_FIRST = """james mary john patricia robert jennifer michael linda william
elizabeth david barbara richard susan joseph jessica thomas sarah charles
karen christopher nancy daniel lisa matthew betty anthony margaret mark
sandra donald ashley steven kimberly paul emily andrew donna joshua michelle
kenneth carol kevin amanda brian melissa george deborah""".split()

#: first name -> its nicknames (query side)
_NICK = {
    "james": "jim jimmy jamie jimbo", "mary": "molly polly mae mamie",
    "john": "jack johnny jon", "patricia": "pat patty trish tricia",
    "robert": "bob rob bobby robbie bert", "jennifer": "jen jenny jenn",
    "michael": "mike mikey mick mickey", "linda": "lindy lin",
    "william": "bill will billy willy liam",
    "elizabeth": "liz beth betsy eliza lizzie libby betty",
    "david": "dave davey davy", "barbara": "barb babs barbie",
    "richard": "dick rich rick ricky richie", "susan": "sue suzy susie",
    "joseph": "joe joey jos", "jessica": "jess jessie",
    "thomas": "tom tommy", "sarah": "sally sadie sara",
    "charles": "chuck charlie chaz chas", "karen": "kari",
    "christopher": "chris kit topher", "nancy": "nan",
    "daniel": "dan danny", "lisa": "lise", "matthew": "matt matty",
    "betty": "bett", "anthony": "tony",
    "margaret": "peggy maggie meg marge greta madge", "mark": "marky",
    "sandra": "sandy sandi", "donald": "don donny", "ashley": "ash",
    "steven": "steve stevie", "kimberly": "kim kimmy", "paul": "paulie",
    "emily": "em emmy millie", "andrew": "andy drew", "joshua": "josh",
    "michelle": "shelly shelley mish", "kenneth": "ken kenny",
    "carol": "carrie caz", "kevin": "kev", "amanda": "mandy manda",
    "brian": "bri", "melissa": "missy mel lissa",
    "george": "georgie geo", "deborah": "deb debbie debby",
}

#: state (dictionary side) -> its postal code
_STATES = {
    "alabama": "al", "alaska": "ak", "arizona": "az", "arkansas": "ar",
    "california": "ca", "colorado": "co", "connecticut": "ct",
    "delaware": "de", "district of columbia": "dc", "florida": "fl",
    "georgia": "ga", "hawaii": "hi", "idaho": "id", "illinois": "il",
    "indiana": "in", "iowa": "ia", "kansas": "ks", "kentucky": "ky",
    "louisiana": "la", "maine": "me", "maryland": "md",
    "massachusetts": "ma", "michigan": "mi", "minnesota": "mn",
    "mississippi": "ms", "missouri": "mo", "montana": "mt",
    "nebraska": "ne", "nevada": "nv", "new hampshire": "nh",
    "new jersey": "nj", "new mexico": "nm", "new york": "ny",
    "north carolina": "nc", "north dakota": "nd", "ohio": "oh",
    "oklahoma": "ok", "oregon": "or", "pennsylvania": "pa",
    "rhode island": "ri", "south carolina": "sc", "south dakota": "sd",
    "tennessee": "tn", "texas": "tx", "utah": "ut", "vermont": "vt",
    "virginia": "va", "washington": "wa", "west virginia": "wv",
    "wisconsin": "wi", "wyoming": "wy",
}

_STREET = "main oak pine maple cedar elm park lake hill river sunset".split()

#: street suffix (dictionary side) -> its standard abbreviation, then the
#: common variants (USPS Publication 28, Appendix C1)
_SUFFIX = {
    "alley": "aly allee ally", "annex": "anx anex annx", "arcade": "arc",
    "avenue": "ave av aven avenu avn avnue", "bayou": "byu bayoo",
    "beach": "bch", "bend": "bnd", "bluff": "blf bluf",
    "bottom": "btm bot bottm", "boulevard": "blvd boul boulv",
    "branch": "br brnch", "bridge": "brg brdge", "brook": "brk",
    "burg": "bg", "bypass": "byp bypa bypas byps", "camp": "cp cmp",
    "canyon": "cyn canyn cnyn", "cape": "cpe", "causeway": "cswy causwa",
    "center": "ctr cen cent centr cnter cntr", "circle": "cir circ circl crcl",
    "cliff": "clf", "club": "clb", "common": "cmn", "corner": "cor",
    "course": "crse", "court": "ct", "cove": "cv", "creek": "crk",
    "crescent": "cres crsent crsnt", "crest": "crst",
    "crossing": "xing crssng", "dale": "dl", "dam": "dm",
    "divide": "dv div dvd", "drive": "dr driv drv", "estate": "est",
    "expressway": "expy exp expr expw", "extension": "ext extn extnsn",
    "falls": "fls", "ferry": "fry frry", "field": "fld", "flat": "flt",
    "ford": "frd", "forest": "frst", "forge": "frg forg", "fork": "frk",
    "fort": "ft frt", "freeway": "fwy freewy frway frwy",
    "garden": "gdn gardn grden grdn", "gateway": "gtwy gatewy gatway gtway",
    "glen": "gln", "green": "grn", "grove": "grv grov",
    "harbor": "hbr harb harbr hrbor", "haven": "hvn", "heights": "hts ht",
    "highway": "hwy highwy hiway hiwy hway", "hill": "hl",
    "hollow": "holw hllw", "inlet": "inlt", "island": "is islnd",
    "junction": "jct jction jctn junctn juncton", "key": "ky",
    "knoll": "knl knol", "lake": "lk", "landing": "lndg lndng",
    "lane": "ln", "light": "lgt", "lock": "lck", "lodge": "ldg ldge lodg",
    "manor": "mnr", "meadow": "mdw", "mill": "ml",
    "mission": "msn missn mssn", "motorway": "mtwy", "mount": "mt",
    "mountain": "mtn mntain mntn mountin mtin", "neck": "nck",
    "orchard": "orch orchrd", "overpass": "opas",
    "parkway": "pkwy parkwy pkway pky", "passage": "psge", "pine": "pne",
    "place": "pl", "plain": "pln", "plaza": "plz plza", "point": "pt",
    "port": "prt", "prairie": "pr prr", "radial": "radl rad radiel",
    "ranch": "rnch", "rapid": "rpd", "rest": "rst", "ridge": "rdg rdge",
    "river": "riv rvr rivr", "road": "rd", "route": "rte",
    "shoal": "shl", "shore": "shr shoar", "skyway": "skwy",
    "spring": "spg spng sprng", "square": "sq sqr sqre squ",
    "station": "sta statn stn", "stream": "strm streme",
    "street": "st strt str", "summit": "smt sumit sumitt",
    "terrace": "ter terr", "trace": "trce", "track": "trak trk",
    "trail": "trl", "tunnel": "tunl tunel tunnl", "turnpike": "tpke trnpk turnpk",
    "union": "un", "valley": "vly vally vlly", "viaduct": "via vdct viadct",
    "view": "vw", "village": "vlg vill villag villg", "ville": "vl",
    "vista": "vis vist vst vsta", "well": "wl",
}


def _rules() -> list[tuple[str, str]]:
    """The first ``RULES`` distinct (query side, dictionary side) pairs,
    kind by kind: nicknames, state codes, short forms of street words,
    then each suffix's standard abbreviation before any variant."""
    pairs = [(nick, name) for name, nicks in _NICK.items()
             for nick in nicks.split()]
    pairs += [(code, state) for state, code in _STATES.items()]
    pairs += [(w[:3], w) for w in _STREET if len(w) >= 5]
    forms = {full: ab.split() for full, ab in _SUFFIX.items()}
    pairs += [(ab[0], full) for full, ab in forms.items()]
    width = max(map(len, forms.values()))
    pairs += [(ab[j], full) for j in range(1, width)
              for full, ab in forms.items() if j < len(ab)]
    kept = list(dict.fromkeys(pairs))
    assert len(kept) >= RULES, len(kept)
    return kept[:RULES]


def make_usps(n: int = 1_000_000, seed: int = 0) -> StringDataset:
    """person name + street + city + state records; nickname, state and
    street-suffix rules."""
    rules = _rules()
    rng = np.random.default_rng(seed)
    firsts = np.array(_FIRST)
    streets = np.array(_STREET)
    # every suffix and state that some rule names, in the lists' order
    named = {rhs for _, rhs in rules}
    stypes = np.array([s for s in _SUFFIX if s in named])
    states = np.array([s for s in _STATES if s in named])
    f = firsts[rng.integers(0, len(firsts), n)]
    l = firsts[rng.integers(0, len(firsts), n)]
    num = rng.integers(1, 9999, n)
    st = streets[rng.integers(0, len(streets), n)]
    ty = stypes[rng.integers(0, len(stypes), n)]
    ct = streets[rng.integers(0, len(streets), n)]
    sa = states[rng.integers(0, len(states), n)]
    strings = [f"{a} {b} {c} {d} {e} {g}ville {h}"
               for a, b, c, d, e, g, h in zip(f, l, num, st, ty, ct, sa)]
    return StringDataset("USPS", strings, _scores(rng, len(strings)),
                         sorted(rules))


make = make_usps
